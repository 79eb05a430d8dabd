"""Jaxpr traces the count engine made in the window (the change in its
``trace_count``).  Moves ``price_p95_ms``."""


def read(ctx):
    c0, c1 = ctx.counts
    return c1["trace_count"] - c0["trace_count"]
