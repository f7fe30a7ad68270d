"""The benchmark's yardstick on the CPU: the dense family's operation counts
checked by hand for a tiny configuration, the peak table, and the trace
reduction on a small recorded trace."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE]

import flops  # noqa: E402
import harness  # noqa: E402
import trace_reduce  # noqa: E402

DENSE = harness.load_family("dense")
TINY = DENSE.Arch(obs_dim=50, num_actions=3, d_model=64, num_heads=4,
            num_kv_heads=2, head_dim=16, d_ff=128, num_layers=2, window=8,
            rope_theta=10000.0, rms_norm_eps=1e-6)


def test_matmul_weights_by_hand():
    # projection 50*64, per layer q 64*4*16 + k,v 2*64*2*16 + o 4*16*64
    # + SwiGLU 3*64*128, head 64*3
    per_layer = 4096 + 4096 + 4096 + 24576
    assert DENSE.matmul_weights(TINY) == 3200 + 2 * per_layer + 192 == 77120


def test_banded_keys_by_hand():
    # positions 0..9 see 1..8 keys, then the window's 8
    assert DENSE.banded_keys(TINY, 10) == 36 + 8 + 8


def test_learner_step_flops_by_hand():
    fwd = 2 * 77120 * (2 * 10) + 4 * 2 * 4 * 16 * (2 * 52)
    assert DENSE.learner_step_flops(TINY, batch=2, seq_len=10) == 4 * fwd


def test_peaks_are_keyed_by_device_kind():
    peak = flops.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        flops.peaks("TPU v99")


@pytest.fixture(scope="module")
def recorded_trace():
    with open(os.path.join(HERE, "testdata", "small_trace.json")) as f:
        return json.load(f)


def test_trace_busy_is_the_union_of_device_intervals(recorded_trace):
    out = trace_reduce.reduce(recorded_trace)
    lo, hi = trace_reduce.window_bounds(recorded_trace)
    intervals = []
    for ops in recorded_trace["device"].values():
        for _, start, end in ops:
            start, end = max(start, lo), min(end, hi)
            if end > start:
                intervals.append((start, end))
    # brute force over the distinct edges
    edges = sorted({x for iv in intervals for x in iv})
    busy = sum(b - a for a, b in zip(edges, edges[1:])
               if any(s <= a and b <= e for s, e in intervals))
    assert out["busy_s"] == pytest.approx(busy / 1e9)
    assert out["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["idle_share"] == pytest.approx(1 - busy / (hi - lo))


def test_trace_gaps_add_up_to_idle_time(recorded_trace):
    out = trace_reduce.reduce(recorded_trace, top=1000)
    idle = out["window_s"] - out["busy_s"]
    assert sum(s for _, s in out["idle_gaps"]) == pytest.approx(idle)
    assert all(label for label, _ in out["idle_gaps"])


def test_trace_op_seconds_name_kernels_stably(recorded_trace):
    out = trace_reduce.reduce(recorded_trace)
    assert out["device_ops"] == sorted(out["device_ops"],
                                       key=lambda kv: -kv[1])
    assert len(out["device_ops"]) <= 10
    assert all(" = " not in name for name in out["op_seconds"])


def test_trace_gap_labels_name_open_spans():
    events = {
        "host": [["bench/window", 0, 100], ["bench/engine.select", 10, 40],
                 ["bench/replay.sample", 30, 90]],
        "device": {"/device:TPU:0": [["m:a", 0, 20], ["m:b", 50, 60]]},
    }
    out = trace_reduce.reduce(events)
    assert out["busy_s"] == pytest.approx(30e-9)
    gaps = dict(out["idle_gaps"])
    # gap 20..50 (mid 35): both spans open; gap 60..100 (mid 80): sample
    assert gaps == {"engine.select+replay.sample": pytest.approx(30e-9),
                    "replay.sample": pytest.approx(40e-9)}


def test_trace_hands_on_the_program_spans(recorded_trace):
    events = {
        "host": [["bench/window", 0, 100], ["bench/learner.step", 10, 90]],
        "device": {"/device:TPU:0": [["m:a", 0, 20]]},
        "program": [["repro/learner/input", 5, 9],
                    ["repro/learner/update", 9, 58]],
    }
    out = trace_reduce.reduce(events)
    assert out["program"] == events["program"]
    assert out["window_ns"] == [0, 100]
    # the program's spans label nothing: the gaps are the benchmark's
    assert dict(out["idle_gaps"]) == {"learner.step": pytest.approx(80e-9)}
    assert trace_reduce.reduce(recorded_trace)["program"] == []
