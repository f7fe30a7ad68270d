"""Share of the window in which no operation ran on the device: 1 minus the
union of the device's operation intervals in the trace over the window."""


def read(ctx):
    share = ctx["trace"]["idle_share"]
    return None if share is None else 100.0 * share
