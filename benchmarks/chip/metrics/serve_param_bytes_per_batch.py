"""Parameter bytes the engine copied to the device per server batch in the
window, in MB (10**6 bytes): the program's counter
``engine/param_bytes_to_device`` over the count of its ``inference/batch``
span."""
import program_counts


def read(ctx):
    batches = program_counts.delta(ctx, "inference/batch_ms", "count")
    copied = program_counts.delta(ctx, "engine/param_bytes_to_device",
                                  "value")
    if not batches or copied is None:
        return None
    return copied / batches / 1e6
