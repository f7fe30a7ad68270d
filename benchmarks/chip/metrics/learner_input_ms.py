"""Time a learner step waited for its batch from the replay, per step in the
window: the program's ``learner/input`` span (around ``next`` on the
replay's stream), its exact sum over its count."""
import program_counts


def read(ctx):
    steps = program_counts.delta(ctx, "learner/input_ms", "count")
    if not steps:
        return None
    return program_counts.delta(ctx, "learner/input_ms", "sum") / steps
