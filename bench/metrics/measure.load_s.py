"""Seconds per profile in the program's ``measure.load`` spans: getting
each battery program (trace, lower, and compile or read it from the
persistent cache) and its first call, blocked until ready.  Moves
``profile_s``."""
from bench import program


def read(ctx):
    return program.per_profile_s(ctx, "measure.load")
