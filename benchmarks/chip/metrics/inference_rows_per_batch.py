"""Rows the engine served (prefill and decode) per server batch in the
window, from the server's exact counters."""


def read(ctx):
    before, after = ctx["before"], ctx["after"]
    batches = after["batches"] - before["batches"]
    if batches <= 0:
        return None
    rows = (after["prefill_rows"] + after["decode_rows"]
            - before["prefill_rows"] - before["decode_rows"])
    return rows / batches
