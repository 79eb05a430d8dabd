#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip it is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell is the ``workloads`` entry of ``BENCHMARK.json`` called
``<name>``.  Its configuration, traffic mix and per-layer readers are
found by name under ``bench/`` (see :mod:`bench.core`); the traffic's
``kind`` picks the loop in ``bench/kinds``.  Set-up builds and warms
everything the window uses, the window measures for ``--seconds``, then
the plain reference (:mod:`bench.reference`) checks what the window
produced.  With ``--trace 1`` the window runs under the profiler and the
result carries the cell's per-layer metrics instead of its end-to-end
ones.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``: each compared number with its limit (also the last lines
of stderr).  Without a TPU, or with fewer chips than the cell asks for,
it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

_T_IMPORT = time.time()

import argparse                                          # noqa: E402
import contextlib                                        # noqa: E402
import json                                              # noqa: E402
import os                                                # noqa: E402
import shutil                                            # noqa: E402
import sys                                               # noqa: E402
from pathlib import Path                                 # noqa: E402

_HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(_HERE.parent), str(_HERE.parent / "src")]

from bench import core                                   # noqa: E402


def _process_start() -> float:
    """Wall time this process started (Linux), else when this file ran."""
    try:
        ticks = os.sysconf("SC_CLK_TCK")
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19]) / ticks
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return time.time() - (up - start)
    except (OSError, ValueError, IndexError):
        return _T_IMPORT


class Harness:
    """What a loop gets: the cell's files and seed, spans, the compile
    meter, and the window."""

    def __init__(self, args, bench_json, bench_dir: Path = core.BENCH):
        self.args = args
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.traced = bool(int(args.trace))
        self.cell = core.find(bench_json["workloads"], args.workload,
                              "workload")
        self.config = core.data_file("configs", self.cell["config"],
                                     bench_dir)
        self.traffic = core.data_file("traffic", self.cell["traffic"],
                                      bench_dir)
        self.bench_dir = bench_dir
        self.spans = core.Spans(traced=self.traced)
        self.meter = None
        self.scratch = core.RUNS / self.cell["name"]
        self.window_wall = None             # (start, end) perf_counter
        self.setup_s = None
        self.compiles = {}
        self.process_start = _process_start()
        # the reference at the next lower precision in the program's place
        self.control = False

    def key(self, *path: int):
        import jax

        k = jax.random.PRNGKey(self.seed % (1 << 32))
        k = jax.random.fold_in(k, self.seed >> 32)
        for p in path:
            k = jax.random.fold_in(k, p)
        return k

    def rng(self, *path: int):
        import numpy as np

        return np.random.default_rng([self.seed, *path])

    @contextlib.contextmanager
    def window(self):
        """The measured window: set-up ends where it starts.  Under
        ``--trace 1`` the profiler records it."""
        import jax

        before = self.meter.snapshot()
        tdir = self.scratch / "trace"
        if self.traced:
            shutil.rmtree(tdir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            # the host's Python calls would swamp the trace and slow the
            # host; level 1 keeps the benchmark's annotations
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(tdir), profiler_options=opts)
        self.setup_s = time.time() - self.process_start
        t0 = time.perf_counter()
        try:
            with self.spans.span("bench.window"):
                yield
        finally:
            self.window_wall = (t0, time.perf_counter())
            if self.traced:
                jax.profiler.stop_trace()
            after = self.meter.snapshot()
            self.compiles = {k: after[k] - before[k] for k in after}

    def finish(self):
        """After the window, before the reference: the device as JAX
        reports it with its peak memory, and under ``--trace 1`` the
        trace reduced to busy time, the window and the breakdown."""
        from bench import trace

        self.device = core.device_info(self.devices,
                                       int(self.cell["chips"]))
        self.trace = None
        if self.traced:
            tr = trace.load(str(self.scratch / "trace"))
            lo, hi = trace.window(tr)
            busy, win = trace.busy_share(tr, lo, hi)
            self.device.update(busy_s=busy, window_s=win)
            self.trace = tr
            self.trace_window = (lo, hi)
            self.breakdown = trace.breakdown(tr, lo, hi)
            (self.scratch / "spans.json").write_text(
                json.dumps(self.spans.rows))
        return self.device

    def ctx(self, **extra):
        """What a per-layer reader reads: the spans, the reduced trace
        and its window, the device kind, and what the loop adds."""
        from types import SimpleNamespace

        return SimpleNamespace(
            spans=self.spans.rows, trace=self.trace,
            trace_window=getattr(self, "trace_window", None),
            kind=self.device["kind"],
            window_s=self.window_wall[1] - self.window_wall[0], **extra)


def main(argv=None, *, require_tpu: bool = True,
         bench_dir: Path = core.BENCH, control: bool = False) -> int:
    """``require_tpu=False`` and ``control`` are for the tests: the first
    skips the look for a chip, the second prices with the reference in
    bfloat16 in the program's place."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench_json = core.load_benchmark(bench_dir.parent)
        h = Harness(args, bench_json, bench_dir)
        h.control = control
        core.enable_compile_cache()
        import jax

        chips = int(h.cell["chips"])
        if require_tpu:
            devices = core.device_gate(chips)
        else:
            devices = jax.devices()
        h.devices = devices
        print(f"device platform={devices[0].platform} "
              f"kind={devices[0].device_kind} count={len(devices)}",
              file=sys.stderr, flush=True)
        h.meter = core.CompileMeter()
        h.scratch.mkdir(parents=True, exist_ok=True)
        drv = core.loop(h.traffic["kind"], bench_dir)
        # the program prints progress; stdout is kept for the result
        with contextlib.redirect_stdout(sys.stderr):
            out = drv.run(h)
    except core.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    out["metrics"].setdefault("setup_s", h.setup_s)
    metrics_cfg = bench_json["per_layer" if h.traced else "end_to_end"]
    metrics = {}
    for m in metrics_cfg:
        if h.cell["name"] not in m.get("workloads", [h.cell["name"]]):
            continue
        if h.traced:
            value = core.reader(m["name"], bench_dir)(out["ctx"])
        else:
            value = out["metrics"].get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = out["checks"]
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics,
              "device": h.device}
    if h.traced:
        result["breakdown"] = h.breakdown
    print(f"compiles in window: {h.compiles}", file=sys.stderr)
    core.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
