"""Self time per profile of the program's ``calibrate.profile`` spans:
each one's duration less the union of its children's intervals, so
what the calibration does outside every named step.  Moves
``profile_s``."""
from bench import program


def read(ctx):
    got = program.spans(ctx)
    roots = [s for s in got if s.name == "calibrate.profile"]
    if not roots:
        return None
    from repro.spans import self_ns

    return sum(self_ns(r, got) for r in roots) / 1e9 / len(roots)
