"""Roofline share of the battery kernels on the chip, in %: the least
time the chip needs for every battery call in the window over the device
time those calls took.  A call's least time is the larger of its
operations over the bf16 peak (float32 work has no published peak and is
bounded by bf16's) and its bytes over the HBM bandwidth
(``bench/roofline.py``).  A kernel's calls are the executions of its
program (``jit_<fn>``) that start inside the benchmark's span around its
timing pass, and their time is those executions' device time.  Kernels
that need no work (the identity) take no part.  Moves ``profile_s``."""
from bench import roofline, trace
from bench.core import trace_spans


def read(ctx):
    if ctx.trace is None:
        return None
    runs = []                       # (operations, bytes, calls, device s)
    for r, lo, hi in trace_spans(ctx, "bench.kernel:"):
        w = roofline.work(r["kernel"], r["tags"])
        if w is None or w == (0.0, 0.0):
            continue
        calls, busy = trace.executions_in(ctx.trace, lo, hi, r["module"])
        if calls and busy > 0:
            runs.append((*w, calls, busy))
    if not runs:
        return None
    peak = roofline.peaks(ctx.kind)
    least = sum(c * roofline.seconds(f, b, peak)[0] for f, b, c, _ in runs)
    return 100.0 * least / sum(d for *_, d in runs)
