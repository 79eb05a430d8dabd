"""Sound runs and the bfloat16 control of one cell, on the chip, in one
process (set-up is paid per run, compiled programs once).

    python3 bench/tests/control.py --workload <name> --seconds <s> \\
        --seeds 11,12,13 --control-seeds 21,22,23

Prints each run's checks, one line per run, and a last summary line: the
largest reading of each check over the sound runs and the smallest over
the control runs, the two readings each limit is set between.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def one(workload: str, seed: int, seconds: float, control: bool) -> dict:
    from bench import run

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", "0"],
                      control=control)
    lines = out.getvalue().strip().splitlines()
    if rc != 0 or not lines:
        raise SystemExit(f"{workload} seed {seed}: exit {rc}")
    return json.loads(lines[-1])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctl = [int(s) for s in args.control_seeds.split(",") if s]
    lower, upper = {}, {}
    for control, group in ((False, seeds), (True, ctl)):
        for seed in group:
            res = one(args.workload, seed, args.seconds, control)
            print(json.dumps({"seed": seed, "control": control,
                              "correct": res["correct"],
                              "metrics": res["metrics"],
                              "checks": res["checks"]}), flush=True)
            for name, c in res["checks"].items():
                if control:
                    upper[name] = min(upper.get(name, float("inf")),
                                      c["value"])
                else:
                    lower[name] = max(lower.get(name, 0.0), c["value"])
    print(json.dumps({"lower": lower, "upper": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
