"""Geometric-mean relative error, in %, of every window profile's prices
of the configuration's subjects against their times measured at set-up.
Moves ``profile_s``, the cell's one end-to-end metric besides set-up: a
wider battery that prices the subjects better costs profile time."""


def read(ctx):
    return getattr(ctx, "price_err_pct", None)
