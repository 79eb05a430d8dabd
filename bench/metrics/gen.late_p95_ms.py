"""How late the load generator sent, at the 95th percentile, in ms: send
time minus due time of every request.  A starved generator reads as a
fast server; this says whether it was.  Moves ``price_p95_ms``."""
from bench.core import percentile


def read(ctx):
    return 1e3 * percentile(ctx.late_s, 95) if ctx.late_s else None
