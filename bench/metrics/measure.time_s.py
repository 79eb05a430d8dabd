"""Seconds per profile in the program's ``measure.time`` spans: each
battery kernel's further warm-up calls and its timed trials.  Moves
``profile_s``."""
from bench import program


def read(ctx):
    return program.per_profile_s(ctx, "measure.time")
