"""Median time a replay sample waited on the rate limiter in the window
(the program's ``replay/sample_block_ms`` histogram, cleared at the
window's start)."""


def read(ctx):
    return ctx["medians"].get("replay/sample_block_ms")
