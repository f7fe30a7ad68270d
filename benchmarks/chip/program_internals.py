"""The benchmark's only reach into the program's private state.

The program's telemetry histograms are reservoirs that run from process
start; the harness clears the ones it reads when the window opens, so that
set-up is left out of their medians.  Until the program's histograms can
be reset through an interface of their own, this module does it, and
raises where a histogram is not the kind it expects, rather than letting a
median silently take in set-up.
"""
from __future__ import annotations


def reset_histograms(names):
    """Clear what the program's histograms ``names`` have recorded."""
    from repro.telemetry import registry
    reg = registry.get_registry()
    if not reg.enabled:
        raise RuntimeError("telemetry is off: no histogram to cut to the "
                           "window")
    for name in names:
        hist = reg.histogram(name)
        if not isinstance(hist, registry.Histogram):
            raise TypeError(f"histogram {name!r} is a {type(hist).__name__}")
        with hist._lock:
            hist._reservoir.clear()
            hist._count = 0
            hist._sum = 0.0
            hist._min = float("inf")
            hist._max = float("-inf")
