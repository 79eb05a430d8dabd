"""Requests per batched evaluation in the window, from the batcher's
``stats()``.  Moves ``price_p95_ms``."""


def read(ctx):
    b0, b1 = ctx.batches
    batches = b1["batches"] - b0["batches"]
    return (b1["requests"] - b0["requests"]) / batches if batches else None
