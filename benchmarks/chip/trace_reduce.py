"""Reduction of a profiler trace to device busy time, kernel time and
labelled idle gaps.

``load`` reads the ``.xplane.pb`` the JAX profiler wrote and keeps three
kinds of event, each as ``[name, start_ns, end_ns]`` on the trace's clock:
the device's operations (plane ``/device:TPU:<n>``, line ``XLA Ops``; the
name is the HLO instruction's, such as ``decode_attention.7``; a Pallas
kernel's instruction carries the kernel's name), the benchmark's own host
spans (``host``: names that start with ``bench/``, written with
``jax.profiler.TraceAnnotation``), and the program's spans (``program``:
names that start with ``repro/``, from ``repro.telemetry.span``).
``reduce`` works on that small form, so a recorded trace can be kept as
JSON beside the tests; it hands the program's spans on unchanged.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List

SPAN_PREFIX = "bench/"
PROGRAM_PREFIX = "repro/"
WINDOW_SPAN = "bench/window"


def load(profile_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    data = ProfileData.from_file(paths[-1])
    device: Dict[str, list] = defaultdict(list)
    host: list = []
    program: list = []
    for plane in data.planes:
        on_device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if on_device and line.name != "XLA Ops":
                continue
            for ev in line.events:
                start = int(ev.start_ns)
                end = start + int(ev.duration_ns)
                if on_device:
                    device[plane.name].append([op_name(ev.name), start, end])
                elif ev.name.startswith(SPAN_PREFIX):
                    host.append([ev.name, start, end])
                elif ev.name.startswith(PROGRAM_PREFIX):
                    program.append([ev.name, start, end])
    return {"device": dict(device), "host": host, "program": program}


def op_name(event_name: str) -> str:
    """The instruction name of a device event, whose name may be the whole
    HLO instruction text (``%fusion.12 = f32[...] fusion(...)``)."""
    return event_name.split(" = ", 1)[0].lstrip(":%")


def _union(intervals: List[list]) -> List[list]:
    merged: List[list] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _clip(start, end, lo, hi):
    return max(start, lo), min(end, hi)


def window_bounds(events: dict):
    """The measured window on the trace's clock: the ``bench/window`` span."""
    spans = [e for e in events["host"] if e[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError("trace holds no bench/window span")
    return spans[0][1], spans[0][2]


def _label_gaps(gaps: List[list], spans: List[list]) -> Dict[str, float]:
    """Seconds of idle gap per label, a gap's label being the benchmark
    spans open at its midpoint."""
    marks = sorted([(s, 1, n) for n, s, e in spans]
                   + [(e, -1, n) for n, s, e in spans])
    out: Dict[str, float] = defaultdict(float)
    open_spans: Dict[str, int] = defaultdict(int)
    i = 0
    for mid, length in sorted(((s + e) // 2, e - s) for s, e in gaps):
        while i < len(marks) and marks[i][0] <= mid:
            open_spans[marks[i][2]] += marks[i][1]
            i += 1
        names = sorted(n[len(SPAN_PREFIX):] for n, c in open_spans.items()
                       if c > 0)
        out["+".join(names) or "no bench span"] += length / 1e9
    return out


def reduce(events: dict, top: int = 10) -> dict:
    """Busy seconds (the union of the device's operations) averaged over the
    traced devices, seconds per operation name, the ``top`` operations by
    device time, the ``top`` idle-gap labels by seconds, the window's bounds
    on the trace's clock, and the program's spans as loaded."""
    lo, hi = window_bounds(events)
    window_s = (hi - lo) / 1e9
    spans = [e for e in events["host"] if e[0] != WINDOW_SPAN]
    busy = []
    op_s: Dict[str, float] = defaultdict(float)
    gap_s: Dict[str, float] = defaultdict(float)
    devices = events["device"]
    for ops in devices.values():
        clipped = []
        for name, start, end in ops:
            start, end = _clip(start, end, lo, hi)
            if end <= start:
                continue
            clipped.append([start, end])
            op_s[name] += (end - start) / 1e9
        merged = _union(clipped)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps = [[s, e] for s, e in zip(edges[0::2], edges[1::2]) if e > s]
        for label, seconds in _label_gaps(gaps, spans).items():
            gap_s[label] += seconds
    count = max(len(devices), 1)
    busy_s = sum(busy) / count
    op_s = {n: s / count for n, s in op_s.items()}
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "op_seconds": op_s,
        "device_ops": [[n, s] for n, s in
                       sorted(op_s.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, s / count] for n, s in
                      sorted(gap_s.items(), key=lambda kv: -kv[1])[:top]],
        "window_ns": [lo, hi],
        "program": events.get("program", []),
    }
