"""The program's own spans (``repro.spans``) that start inside the window.

The program keeps its finished spans in memory on ``perf_counter_ns``;
the window's ``bench.window`` row in ``ctx.spans`` is on
``perf_counter`` too, so no clock mapping is needed to pick them.  To
put them on the device trace's clock, :func:`to_trace` maps them
linearly through both ends of ``bench.window``, which the trace also
holds.  A program that records no spans gives nothing here, and each
reader built on it then returns None.
"""
from __future__ import annotations

from typing import Callable, List, Optional


def window(ctx):
    """``(start, end)`` of ``bench.window`` in ``perf_counter`` seconds,
    or None."""
    rows = [r for r in ctx.spans if r["name"] == "bench.window"]
    return (rows[-1]["start"], rows[-1]["end"]) if rows else None


def recorded(lo_ns: int, hi_ns: int) -> list:
    """The program's finished spans that start in ``[lo_ns, hi_ns)``;
    none where the program has no recorder."""
    try:
        from repro import spans
    except ImportError:
        return []
    return spans.between(lo_ns, hi_ns)


def spans(ctx) -> list:
    win = window(ctx)
    if win is None:
        return []
    return recorded(int(win[0] * 1e9), int(win[1] * 1e9))


def per_profile_s(ctx, name: str) -> Optional[float]:
    """Seconds in spans ``name`` over the window's calibrations (its
    ``calibrate.profile`` spans); None where either is missing."""
    got = spans(ctx)
    profiles = sum(s.name == "calibrate.profile" for s in got)
    ns = [s.t1_ns - s.t0_ns for s in got if s.name == name]
    if not profiles or not ns:
        return None
    return sum(ns) / 1e9 / profiles


def to_trace(ctx) -> Optional[Callable[[int], float]]:
    """``perf_counter_ns`` -> trace ns, the line through both ends of
    ``bench.window`` as ``ctx.spans`` and the trace each hold it; None
    without a trace."""
    win = window(ctx)
    if ctx.trace is None or win is None or ctx.trace_window is None:
        return None
    p0, p1 = win[0] * 1e9, win[1] * 1e9
    t0, t1 = ctx.trace_window
    scale = (t1 - t0) / (p1 - p0)
    return lambda ns: t0 + (ns - p0) * scale


def overlap_ns(a: List[tuple], b: List[tuple]) -> float:
    """Length of the intersection of two sorted, disjoint interval
    lists (as ``trace.union`` gives them)."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total
