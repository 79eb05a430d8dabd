"""Seconds per profile in the program's ``solve.identify`` spans: the
static identifiability analysis of every zoo rung before the fits.
Moves ``profile_s``."""
from bench import program


def read(ctx):
    return program.per_profile_s(ctx, "solve.identify")
