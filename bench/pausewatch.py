#!/usr/bin/env python3
"""Find the stops of one benchmark run, and what ran while it stood.

    python3 bench/pausewatch.py --out <dir> -- --workload <name> \\
        --seed <n> --seconds <s> --trace 0

Runs ``bench/run.py``'s ``main`` in this process, with two witnesses
that do not rest on one another:

* a ticker thread that wakes every 10 ms; a gap of more than ``--gap``
  seconds between two ticks is a stop of Python in this process;
* a watcher process (this file with ``--watch <pid>``) that imports
  nothing of the program: it sleeps 10 ms in a loop (its own gaps are
  stops of the whole host) and every 50 ms reads each thread's CPU time
  and state from ``/proc/<pid>/task``, the process's page faults, and
  the host's steal and I/O-wait time from ``/proc/stat``.

For each stop it reports when it fell (set-up or window), how long it
was, the CPU time each thread of this process used inside it, named
where the thread is a Python thread, the threads seen in a state other
than sleeping (``R`` running, ``D`` waiting on the disk or a page, ``T``
stopped), the process's page faults, the host's steal and I/O wait, and the
watcher's longest gap; and each full collection of Python's collector
over ``--gap``.  A thread that used CPU for about the whole stop held
the GIL; a stop in which the watcher stood too was a stop of the host.
(faulthandler's stack dump from a thread without the GIL would say
where; it is left out because it reads running threads' frames, and two
runs of the price cell on the chip died of SIGSEGV while it wrote.)  All clocks are
``time.monotonic``, which both processes share.  The run's own result
line is printed first, then the report, which ``<dir>/pauses.json``
also holds.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
TICK_S, SAMPLE_S = 0.01, 0.05


def _stat(path: str):
    """(state, minflt, majflt, utime + stime in ticks) of a stat file."""
    with open(path) as f:
        rest = f.read().rsplit(")", 1)[1].split()
    return rest[0], int(rest[7]), int(rest[9]), int(rest[11]) + int(rest[12])


def _host():
    """(steal, iowait) ticks of all CPUs together."""
    with open("/proc/stat") as f:
        cpu = f.readline().split()[1:]
    return int(cpu[7]) if len(cpu) > 7 else 0, int(cpu[4])


def watch(pid: int, out: Path) -> None:
    """The watcher: one JSON line a sample until ``pid`` is gone."""
    hz = os.sysconf("SC_CLK_TCK")
    last, prev = time.monotonic(), {}
    with open(out, "w") as f:
        while True:
            time.sleep(TICK_S)
            now = time.monotonic()
            gap, last = now - last, now
            if gap < SAMPLE_S and now - prev.get("t", 0.0) < SAMPLE_S:
                continue
            try:
                _, minflt, majflt, _ = _stat(f"/proc/{pid}/stat")
                threads = {}
                for tid in os.listdir(f"/proc/{pid}/task"):
                    with contextlib.suppress(OSError):
                        st, _, _, cpu = _stat(f"/proc/{pid}/task/{tid}/stat")
                        with open(f"/proc/{pid}/task/{tid}/comm") as c:
                            threads[tid] = (st, cpu, c.read().strip())
            except OSError:
                return
            steal, iowait = _host()
            row = {"t": now, "gap": gap, "minflt": minflt, "majflt": majflt,
                   "steal_s": steal / hz, "iowait_s": iowait / hz,
                   "cpu": {t: v[1] / hz for t, v in threads.items()},
                   "run": {t: v[0] for t, v in threads.items()
                           if v[0] != "S"},
                   "comm": {t: v[2] for t, v in threads.items()
                            if t not in prev.get("cpu", {})}}
            f.write(json.dumps(row) + "\n")
            f.flush()
            prev = row


class Ticker(threading.Thread):
    """Wakes every 10 ms and records gaps."""

    def __init__(self, gap: float):
        super().__init__(name="pausewatch.ticker", daemon=True)
        self.gap = gap
        self.stops, self.halt = [], threading.Event()
        self.names = {}             # native thread id -> Python name

    def run(self):
        last, n = time.monotonic(), 0
        while not self.halt.is_set():
            time.sleep(TICK_S)
            now = time.monotonic()
            if now - last > self.gap:
                self.stops.append((last, now))
            last, n = now, n + 1
            if n % 100 == 1:
                self.names.update((t.native_id, t.name)
                                  for t in threading.enumerate())


def report(stops, collections, marks, samples, names):
    """Each stop with what the watcher saw inside it."""
    def phase(t):
        w = marks.get("window")
        if not w or t < w[0]:
            return "set-up"
        return "window" if w[1] is None or t <= w[1] else "after"

    comm = {}
    for s in samples:
        comm.update(s["comm"])
    out = []
    for a, b in stops:
        inside = [s for s in samples if a < s["t"] <= b + SAMPLE_S]
        before = [s for s in samples if s["t"] <= a]
        row = {"phase": phase(a), "at_s": a - marks["start"],
               "stop_s": b - a}
        if inside and before:
            s0, s1 = before[-1], inside[-1]
            used = {t: c - s0["cpu"].get(t, 0.0) for t, c in s1["cpu"].items()}
            top = sorted(used.items(), key=lambda kv: -kv[1])[:5]
            row.update(
                process_cpu_s=sum(used.values()),
                threads=[[names.get(int(t), comm.get(t, "?")), round(c, 3)]
                         for t, c in top if c > 0],
                states=sorted({f"{names.get(int(t), comm.get(t, '?'))}:{st}"
                               for s in inside for t, st in s["run"].items()}),
                majflt=s1["majflt"] - s0["majflt"],
                minflt=s1["minflt"] - s0["minflt"],
                host_steal_s=s1["steal_s"] - s0["steal_s"],
                host_iowait_s=s1["iowait_s"] - s0["iowait_s"],
                watcher_gap_s=max(s["gap"] for s in inside))
        out.append(row)
    return {"stops": out, "collections_over_gap": collections,
            "watcher_samples": len(samples)}


def watched(fn, out: Path, gap: float = 0.2):
    """``fn()`` under the two witnesses: its return value and the
    report.  ``marks`` gets the window's ends from :func:`main`."""
    marks = watched.marks = {"start": time.monotonic()}
    collections, t_gc = [], {}

    def on_gc(phase, info):
        if phase == "start":
            t_gc["t"] = time.monotonic()
        elif info["generation"] == 2 and "t" in t_gc:
            dt = time.monotonic() - t_gc["t"]
            if dt > gap:
                collections.append([t_gc["t"] - marks["start"], dt])

    gc.callbacks.append(on_gc)
    log = out / "watcher.jsonl"
    watcher = subprocess.Popen([sys.executable, __file__, "--out", str(out),
                                "--watch", str(os.getpid())])
    ticker = Ticker(gap)
    ticker.start()
    try:
        rc = fn()
    finally:
        ticker.halt.set()
        ticker.join()
        gc.callbacks.remove(on_gc)
        watcher.terminate()
        watcher.wait()
    samples = [json.loads(ln) for ln in log.read_text().splitlines() if ln]
    log.unlink()
    rep = report(ticker.stops, collections, marks, samples, ticker.names)
    rep["window_s"] = [w - marks["start"] for w in marks.get("window", [])
                       if w is not None]
    (out / "pauses.json").write_text(json.dumps(rep, indent=1))
    return rc, rep


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--gap", type=float, default=0.2)
    ap.add_argument("--watch", type=int, help=argparse.SUPPRESS)
    ap.add_argument("run_args", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    if args.watch is not None:
        watch(args.watch, out / "watcher.jsonl")
        return 0

    sys.path[:0] = [str(HERE.parent)]
    from bench import run

    window = run.Harness.window

    @contextlib.contextmanager
    def marked(self):
        with window(self):
            watched.marks["window"] = [time.monotonic(), None]
            try:
                yield
            finally:
                watched.marks["window"][1] = time.monotonic()

    run.Harness.window = marked
    rc, rep = watched(
        lambda: run.main([a for a in args.run_args if a != "--"]), out,
        args.gap)
    print(json.dumps(rep), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
