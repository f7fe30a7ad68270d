"""Learner step time: the change in ``JaxLearner.learner_walltime`` (host
clock around each jitted step, ended by ``block_until_ready``) over the
steps taken in the window."""


def read(ctx):
    steps = ctx["after"]["learner_steps"] - ctx["before"]["learner_steps"]
    if steps <= 0:
        return None
    wall = ctx["after"]["learner_walltime"] - ctx["before"]["learner_walltime"]
    return 1000.0 * wall / steps
