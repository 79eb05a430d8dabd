"""Trim a traced run's trace into a test fixture of the trace reduction.

    python3 bench/tests/mkfixture.py <name> runs/bench/<cell>

reads ``<run dir>/trace`` (the profiler's ``.xplane.pb``) and
``<run dir>/spans.json`` that a ``--trace 1`` run leaves, keeps the
events of the window's first profile (``bench.profile``; the window's
first second where there is none), and writes
``bench/fixtures/trace_<name>.json.gz`` and ``spans_<name>.json``, which
the trace tests pick up by name.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace  # noqa: E402


def main(name: str, run_dir: str) -> None:
    run = Path(run_dir)
    tr = trace.load(str(run / "trace"))
    spans = json.loads((run / "spans.json").read_text())
    lo, hi = trace.window(tr)
    win = [r for r in spans if r["name"] == "bench.window"][-1]
    off = lo - int(win["start"] * 1e9)       # host clock -> trace clock
    prof = [r for r in spans if r["name"] == "bench.profile"
            and r["start"] >= win["start"]]
    start, end = ((prof[0]["start"], prof[0]["end"]) if prof
                  else (win["start"], win["start"] + 1.0))
    plo, phi = int(start * 1e9) + off, int(end * 1e9) + off
    t = trace.trimmed(tr, plo, phi)
    t["spans"] = [s for s in t["spans"] if s[0] != "bench.window"] \
        + [["bench.window", plo, phi - plo]]
    keep = [r for r in spans if start <= r["start"] < end
            and r["name"] != "bench.window"]
    keep.append({"name": "bench.window", "start": start, "end": end})
    out = ROOT / "bench" / "fixtures"
    out.mkdir(exist_ok=True)
    trace.save(t, out / f"trace_{name}.json.gz")
    (out / f"spans_{name}.json").write_text(json.dumps(keep))


if __name__ == "__main__":
    main(*sys.argv[1:3])
