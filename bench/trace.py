"""The reduction from a profiler trace to the numbers the readers use.

A trace is reduced to plain lists first (:func:`load`): the device
operations of each chip, the XLA module executions, and the host spans the
benchmark annotated, all in nanoseconds on one clock.  Everything after
that is arithmetic over those lists, checked in the tests on a synthetic
trace and on trimmed copies of traces recorded on the chip
(``bench/fixtures``).
"""
from __future__ import annotations

import glob
import gzip
import json
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Tuple

Interval = Tuple[int, int]          # (start_ns, end_ns)

#: host annotations the benchmark writes all start with this
SPAN_PREFIX = "bench."


def _is_device(plane: str) -> bool:
    return plane.startswith("/device:TPU:")


def _short(name: str) -> str:
    """An op's name without its HLO text: ``%while.181 = (f32[3,3]...``
    is ``while.181``."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(trace_dir: str) -> Dict[str, object]:
    """The profiler's ``.xplane.pb`` under ``trace_dir`` as plain lists:

    * ``ops``: ``{device: [[name, start_ns, dur_ns], ...]}`` from each
      chip's "XLA Ops" line;
    * ``modules``: ``{device: [[name, start_ns, dur_ns], ...]}`` from its
      "XLA Modules" line (one per program execution);
    * ``spans``: ``[[name, start_ns, dur_ns], ...]``, the host
      annotations whose name starts with ``bench.``.
    """
    import jax

    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(sorted(files)[-1])
    ops: Dict[str, list] = defaultdict(list)
    modules: Dict[str, list] = defaultdict(list)
    spans: list = []
    for plane in pd.planes:
        if _is_device(plane.name):
            for line in plane.lines:
                target = {"XLA Ops": ops,
                          "XLA Modules": modules}.get(line.name)
                if target is None:
                    continue
                for e in line.events:
                    target[plane.name].append(
                        [_short(e.name), int(e.start_ns), int(e.duration_ns)])
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, int(e.start_ns),
                                      int(e.duration_ns)])
    return {"ops": dict(ops), "modules": dict(modules), "spans": spans}


def save(trace: Dict[str, object], path: Path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def read(path: Path) -> Dict[str, object]:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of the intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_ns(intervals: List[Interval], lo: int, hi: int) -> int:
    """Length of the union of the intervals inside [lo, hi)."""
    return sum(e - s for s, e in union(clip(intervals, lo, hi)))


def op_intervals(trace, device: str) -> List[Interval]:
    return [(s, s + d) for _, s, d in trace["ops"].get(device, [])]


def devices(trace) -> List[str]:
    return sorted(trace["ops"])


def window(trace, name: str = "bench.window") -> Interval:
    """The benchmark's window span; failing that, all the trace holds."""
    for n, s, d in trace["spans"]:
        if n == name:
            return s, s + d
    events = [e for lines in (trace["ops"], trace["modules"])
              for evs in lines.values() for e in evs] + trace["spans"]
    return (min(e[1] for e in events), max(e[1] + e[2] for e in events))


def busy_share(trace, lo: int, hi: int) -> Tuple[float, float]:
    """(busy seconds averaged over the chips, window seconds)."""
    devs = devices(trace)
    if not devs or hi <= lo:
        return 0.0, (hi - lo) / 1e9
    busy = sum(busy_ns(op_intervals(trace, d), lo, hi) for d in devs)
    return busy / len(devs) / 1e9, (hi - lo) / 1e9


def idle_pct(trace, win: Interval = None):
    """Share of the window in which no operation ran on the chips, in %:
    1 - busy / window; None without a trace."""
    if trace is None or win is None:
        return None
    busy, length = busy_share(trace, *win)
    return 100.0 * (1.0 - busy / length) if length > 0 else None


def op_seconds(trace, lo: int, hi: int) -> Dict[str, float]:
    """Device seconds per operation name inside [lo, hi), over all chips
    (each op clipped to the window)."""
    out: Dict[str, float] = defaultdict(float)
    for dev in devices(trace):
        for name, s, d in trace["ops"][dev]:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                out[name] += (b - a) / 1e9
    return dict(out)


def executions_in(trace, lo: int, hi: int, module: str = ""
                  ) -> Tuple[int, float]:
    """(count, device seconds) of the executions of programs named
    ``module...`` that start inside [lo, hi), over all chips."""
    runs = [d for dev in trace["modules"]
            for name, s, d in trace["modules"][dev]
            if lo <= s < hi and name.startswith(module)]
    return len(runs), sum(runs) / 1e9


def idle_gaps(trace, lo: int, hi: int) -> Dict[str, float]:
    """Idle seconds of the first chip inside [lo, hi), each gap split by
    the innermost benchmark span the host was in (``host`` where none),
    spans of one kind (``bench.kernel:<name>``) taken together."""
    devs = devices(trace)
    if not devs:
        return {}
    busy = union(clip(op_intervals(trace, devs[0]), lo, hi))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    spans = sorted(((s, s + d, n.split(":")[0]) for n, s, d in trace["spans"]
                    if n != "bench.window"), key=lambda x: x[1] - x[0])
    out: Dict[str, float] = defaultdict(float)
    for gs, ge in gaps:
        # cut each gap at span edges, and give each piece to the
        # shortest span that covers it
        cuts = sorted({gs, ge, *(x for s, e, _ in spans
                                 for x in (s, e) if gs < x < ge)})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            label = next((n for s, e, n in spans if s <= mid < e), "host")
            out[label] += (b - a) / 1e9
    return dict(out)


def breakdown(trace, lo: int, hi: int, top: int = 10) -> Dict[str, list]:
    ops = sorted(op_seconds(trace, lo, hi).items(), key=lambda kv: -kv[1])
    gaps = sorted(idle_gaps(trace, lo, hi).items(), key=lambda kv: -kv[1])
    return {"device_ops": [[n, s] for n, s in ops[:top]],
            "idle_gaps": [[n, s] for n, s in gaps[:top]]}


def trimmed(trace, lo: int, hi: int, devices_kept: int = 1
            ) -> Dict[str, object]:
    """The events of ``trace`` that start in [lo, hi), for a fixture."""
    keep = devices(trace)[:devices_kept]
    return {
        "ops": {d: [e for e in trace["ops"][d] if lo <= e[1] < hi]
                for d in keep},
        "modules": {d: [e for e in trace["modules"].get(d, [])
                        if lo <= e[1] < hi] for d in keep},
        "spans": [e for e in trace["spans"]
                  if lo <= e[1] < hi or e[0] == "bench.window"],
    }
