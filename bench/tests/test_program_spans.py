"""The readers of the program's own spans (``bench/program.py`` and the
six metrics built on it), by hand on synthetic spans and a synthetic
trace, and on the ``tiny.calibrate`` fixture cell traced on the CPU.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest bench/tests -q
"""
from __future__ import annotations

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT), str(ROOT / "src")]

from bench import core, program  # noqa: E402
from repro.spans import Span  # noqa: E402
from test_bench import _run, tree  # noqa: E402,F401  (tree: a fixture)

SPAN_TIMES = ["measure.load_s", "measure.time_s", "count.battery_s",
              "solve.identify_s", "calibrate.self_s"]

#: the window on perf_counter: 100.000 s to 100.010 s
P0 = 100_000_000_000
#: the same window on the trace's clock: offset, and a drift of 1e-3
T0, DRIFT = 7_000_000, 1.001


def _ctx(got, monkeypatch, trace=None):
    """``got`` stands for the program's recorded spans; None leaves
    ``program.recorded`` to read the program's recorder."""
    if got is not None:
        monkeypatch.setattr(
            program, "recorded",
            lambda lo, hi: [s for s in got if lo <= s.t0_ns < hi])
    win = {"name": "bench.window", "start": P0 / 1e9,
           "end": (P0 + 10_000_000) / 1e9}
    return SimpleNamespace(
        spans=[win], trace=trace, kind="TPU v5 lite",
        trace_window=(T0, T0 + round(10_000_000 * DRIFT)))


def _span(id_, parent, name, t0, t1, root=1):
    return Span(id_, parent, root, name, P0 + t0, P0 + t1, 0, {})


def test_clock_mapping_and_time_idle_by_hand(monkeypatch):
    got = [_span(1, 0, "calibrate.profile", 0, 9_000_000),
           _span(2, 1, "measure.time", 1_000_000, 3_000_000),
           _span(3, 1, "measure.time", 5_000_000, 6_000_000)]
    # on the trace: [8_001_000, 10_003_000) and [12_005_000, 13_006_000)
    ops = [["fusion", 8_001_000, 500_000],       # inside the first
           ["copy", 8_201_000, 100_000],         # under the first op
           ["fusion", 11_000_000, 500_000],      # between the two
           ["add", 12_906_000, 600_000]]         # over the second's end
    tr = {"ops": {"/device:TPU:0": ops}, "modules": {}, "spans": []}
    ctx = _ctx(got, monkeypatch, tr)
    to_trace = program.to_trace(ctx)
    assert to_trace(P0) == T0
    assert to_trace(P0 + 10_000_000) == pytest.approx(T0 + 10_010_000)
    assert to_trace(P0 + 5_000_000) == pytest.approx(12_005_000)
    busy, timed = 500_000 + 100_000, 2_002_000 + 1_001_000
    want = 100 * (1 - busy / timed)
    assert core.reader("measure.time_idle_pct")(ctx) == pytest.approx(want)
    # without the drift the second span would end 6 us early, and hold
    # less of the last op
    assert want != pytest.approx(100 * (1 - (500_000 + 94_000)
                                        / (2_000_000 + 1_000_000)))
    assert core.reader("measure.time_s")(ctx) == pytest.approx(3e-3)


def test_self_time_and_per_profile_by_hand(monkeypatch):
    got = [_span(1, 0, "calibrate.profile", 0, 1000),
           _span(2, 1, "measure.gather", 100, 400),
           _span(3, 1, "solve.identify", 300, 500),     # overlaps 2
           _span(4, 1, "calibrate.save", 900, 1200),    # past the end
           _span(5, 2, "measure.load", 150, 200),       # a grandchild
           _span(6, 0, "calibrate.profile", 2000, 2600, root=6),
           _span(7, 6, "measure.load", 2100, 2130, root=6),
           _span(8, 0, "price.open", 2700, 2800, root=8)]
    ctx = _ctx(got, monkeypatch)
    # first profile: children cover [100, 500) and [900, 1000): 500 ns
    # self; second: 600 - 30
    assert core.reader("calibrate.self_s")(ctx) \
        == pytest.approx((500 + 570) / 2 / 1e9)
    assert core.reader("measure.load_s")(ctx) == pytest.approx(40 / 1e9)
    assert core.reader("solve.identify_s")(ctx) \
        == pytest.approx(100 / 1e9)
    # a span that starts outside the window is not the window's
    assert program.spans(_ctx([_span(9, 0, "calibrate.profile",
                                     -5, 100)], monkeypatch)) == []


@pytest.mark.parametrize("program_has", ["no_spans", "no_recorder"])
def test_nothing_recorded_reads_none(monkeypatch, program_has):
    """A window with no program spans, and an older program that has no
    ``repro.spans`` to import: every reader returns None."""
    tr = {"ops": {"/device:TPU:0": [["f", 8_000_000, 10]]}, "modules": {},
          "spans": []}
    if program_has == "no_spans":
        ctx = _ctx([], monkeypatch, tr)
    else:
        import repro
        monkeypatch.setitem(sys.modules, "repro.spans", None)
        monkeypatch.delattr(repro, "spans")
        ctx = _ctx(None, monkeypatch, tr)
        assert program.recorded(0, 1 << 62) == []
    for name in SPAN_TIMES + ["measure.time_idle_pct"]:
        assert core.reader(name)(ctx) is None, name


def test_tiny_calibrate_traced(tree, tmp_path, monkeypatch):  # noqa: F811
    """The fixture cell traced on the CPU: the five span times are there
    and, with what they leave out, fit inside the mean profile."""
    from repro import spans

    # a scratch directory of its own, apart from test_bench's runs
    monkeypatch.setattr(core, "RUNS", tmp_path)
    rc, res = _run(tree, "tiny.calibrate", seconds=2, trace_on=1)
    assert rc == 0 and res["correct"], res
    got = {m: res["metrics"][m]["value"] for m in SPAN_TIMES}
    assert all(v > 0 for v in got.values()), got
    # the CPU has no device plane to read
    assert "measure.time_idle_pct" not in res["metrics"]
    rows = json.loads((core.RUNS / "tiny.calibrate" / "spans.json")
                      .read_text())
    (win,) = [r for r in rows if r["name"] == "bench.window"]
    profiles = [s for s in spans.between(int(win["start"] * 1e9),
                                         int(win["end"] * 1e9))
                if s.name == "calibrate.profile"]
    mean = sum(s.seconds for s in profiles) / len(profiles)
    assert sum(got.values()) <= mean
