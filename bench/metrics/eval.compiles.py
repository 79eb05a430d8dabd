"""Traces of the batched evaluator in the window (the change in
``PredictEngine.trace_count``): each is a compile for a row count set-up
did not warm.  Moves ``price_p95_ms``."""


def read(ctx):
    t0, t1 = ctx.eval_traces
    return t1 - t0
