"""Count-cache hits over lookups in the window, in % (the change in
``CountEngine.stats()``).  Moves ``price_p95_ms``."""


def read(ctx):
    c0, c1 = ctx.counts
    hits = c1["hits"] - c0["hits"]
    looks = hits + c1["misses"] - c0["misses"]
    return 100.0 * hits / looks if looks else None
