"""The learner's share of the chip's bf16 peak: operations one SGD step
needs (counted from shapes by the configuration's family) times the steps
completed in the window, over the window and the peak."""


def read(ctx):
    if ctx["peak"] is None:
        return None
    steps = ctx["steps_in_window"]
    if steps <= 0:
        return None
    learner = ctx["traffic"]["learner"]
    per_step = ctx["family"].learner_step_flops(
        ctx["arch"], learner["batch_size"], learner["sequence_length"])
    peak = ctx["peak"]["bf16_flops_per_s"]
    return 100.0 * per_step * steps / ctx["window_s"] / peak
