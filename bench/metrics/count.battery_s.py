"""Seconds per profile in the program's ``count.batch`` spans: the
battery's counts from the count engine (its symbolic families rebuilt
from probe traces).  Moves ``profile_s``."""
from bench import program


def read(ctx):
    return program.per_profile_s(ctx, "count.batch")
