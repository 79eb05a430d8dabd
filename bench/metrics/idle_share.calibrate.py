"""Share of the traced window in which no operation ran on the chip:
1 - (union of device op intervals) / window.  Moves ``profile_s``."""
from bench import trace


def read(ctx):
    return trace.idle_pct(ctx.trace, ctx.trace_window)
