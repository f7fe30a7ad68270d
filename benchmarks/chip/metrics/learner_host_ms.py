"""Host time of a learner step after its update, per step in the window:
the program's ``learner/priorities`` span (the replay's priority callback)
and ``learner/metrics`` span (one ``device_get`` of the metrics), their
exact sums over the count of the second, which closes every step."""
import program_counts


def read(ctx):
    steps = program_counts.delta(ctx, "learner/metrics_ms", "count")
    if not steps:
        return None
    host = program_counts.delta(ctx, "learner/metrics_ms", "sum")
    host += program_counts.delta(ctx, "learner/priorities_ms", "sum") or 0.0
    return host / steps
