"""Share, in %, of the time inside the program's ``measure.time`` spans
in which the chip ran no operation: how far the battery's timed calls
are dispatch.  The spans are mapped onto the trace's clock through both
ends of ``bench.window`` (``bench/program.py``), and their union is
intersected with the union of each chip's operations.  Moves
``profile_s``."""
from bench import program, trace


def read(ctx):
    to_trace = program.to_trace(ctx)
    if to_trace is None:
        return None
    timed = trace.union([(to_trace(s.t0_ns), to_trace(s.t1_ns))
                         for s in program.spans(ctx)
                         if s.name == "measure.time"])
    devs = trace.devices(ctx.trace)
    if not timed or not devs:
        return None
    lo, hi = timed[0][0], timed[-1][1]
    total = sum(e - s for s, e in timed)
    busy = sum(program.overlap_ns(
        timed, trace.union(trace.clip(trace.op_intervals(ctx.trace, d),
                                      lo, hi)))
        for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / total)
