"""Median time a replay insert waited on the rate limiter in the window
(the program's ``replay/insert_block_ms`` histogram, cleared at the
window's start)."""


def read(ctx):
    return ctx["medians"].get("replay/insert_block_ms")
