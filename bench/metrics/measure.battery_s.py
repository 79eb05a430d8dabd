"""Seconds per profile in the measurement layer (``core.uipick``): the
benchmark's span around ``gather_feature_table``, averaged over the
window's profiles.  Moves ``profile_s``."""
from bench.core import mean_span_s


def read(ctx):
    return mean_span_s(ctx.spans, "bench.measure")
