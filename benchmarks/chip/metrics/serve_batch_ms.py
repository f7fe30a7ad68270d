"""Time the inference server spent on one batch in the window: the
program's ``inference/batch`` span (around the engine call that answers the
batch), its exact sum over its count."""
import program_counts


def read(ctx):
    batches = program_counts.delta(ctx, "inference/batch_ms", "count")
    if not batches:
        return None
    return program_counts.delta(ctx, "inference/batch_ms", "sum") / batches
