"""Seconds per profile in the solver (``core.calibrate``): the benchmark's
span around the zoo fits (``fit_models``), averaged over the window's
profiles.  Moves ``profile_s``."""
from bench.core import mean_span_s


def read(ctx):
    return mean_span_s(ctx.spans, "bench.solve")
