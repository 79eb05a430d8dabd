"""What every cell shares: finding its files by name, the device gate, the
compile cache, the compile meter, spans and the result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Its
configuration is ``bench/configs/<config>.json``, its traffic mix
``bench/traffic/<traffic>.json`` (whose ``kind`` names the loop in
``bench/kinds/<kind>.py``), and each per-layer metric a reader
``bench/metrics/<metric>.py``.  Nothing here names a cell, so a new one
is new files plus new entries.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: fixed, inside the checkout: the path is part of the cache's key
COMPILE_CACHE = ROOT / ".jax_cache"
#: per-run scratch (profiles, measurement caches, traces), inside the checkout
RUNS = ROOT / "runs" / "bench"


class BenchError(RuntimeError):
    """A cell that cannot run as asked: unknown name, no chip, bad file."""


# ---------------------------------------------------------------------------
# finding a cell's files by name
# ---------------------------------------------------------------------------


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def find(entries: List[Dict[str, Any]], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json; known: "
                     f"{sorted(e['name'] for e in entries)}")


def data_file(kind: str, name: str, bench: Path = BENCH) -> Dict[str, Any]:
    """``bench/<kind>/<name>.json``: a configuration or a traffic mix."""
    path = bench / kind / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no {kind} file {path}")
    return json.loads(path.read_text())


def load_module(path: Path, name: str):
    """Import a file whose name need not be an identifier (metric names
    carry dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise BenchError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, bench: Path = BENCH) -> Callable:
    path = bench / "metrics" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no reader {path} for per-layer metric {name!r}")
    return load_module(path, f"bench_metric_{len(name)}_{abs(hash(name))}"
                       ).read


def loop(kind: str, bench: Path = BENCH):
    path = bench / "kinds" / f"{kind}.py"
    if not path.is_file():
        raise BenchError(f"no loop {path} for traffic kind {kind!r}")
    return load_module(path, f"bench_kind_{kind}")


# ---------------------------------------------------------------------------
# the chip
# ---------------------------------------------------------------------------


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``,
    whatever the environment says: the two sides of a comparison must not
    share one.  Set before the program reads the variable."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(COMPILE_CACHE)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
    # the battery compiles in well under JAX's 1 s caching floor
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # no eviction, whatever size limit the environment sets: the cache is
    # the checkout's own, and with eviction on one entry written without
    # its access-time file fails every later write, so nothing is cached
    jax.config.update("jax_compilation_cache_max_size", -1)
    return str(COMPILE_CACHE)


def device_gate(chips: int):
    """The TPU chips JAX sees, or :class:`BenchError`: there is no CPU
    path, and no result is printed without the chips the cell asks for."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise BenchError(f"JAX found no backend: {e}") from e
    if devices[0].platform != "tpu":
        raise BenchError(f"JAX's default backend is "
                         f"{devices[0].platform!r}, not a TPU")
    if len(devices) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices


def device_info(devices, chips: int) -> Dict[str, Any]:
    used = devices[:chips]
    peaks = []
    for d in used:
        try:
            peaks.append(int(d.memory_stats()["peak_bytes_in_use"]))
        except (KeyError, TypeError, RuntimeError, AttributeError):
            pass
    info = {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used)}
    if peaks:
        info["memory_peak_bytes"] = max(peaks)
    return info


class CompileMeter:
    """Backend compiles and persistent-cache traffic, from JAX's monitoring
    events.  A cache hit is recorded as a backend compile lasting only the
    read, so ``misses`` counts what XLA really compiled."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> Dict[str, float]:
        return {"compiles": self.compiles, "hits": self.hits,
                "misses": self.misses, "seconds": self.seconds}


# ---------------------------------------------------------------------------
# spans: the benchmark's own, around its calls into each layer
# ---------------------------------------------------------------------------


class Spans:
    """Host spans on ``time.perf_counter``.  With ``traced`` each span is
    also a profiler annotation, so the trace reduction can put device time
    under the span the host was in."""

    def __init__(self, traced: bool = False):
        self.traced = traced
        self.rows: List[Dict[str, Any]] = []

    @contextlib.contextmanager
    def span(self, name: str, **meta):
        ann = contextlib.nullcontext()
        if self.traced:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
        t0 = time.perf_counter()
        try:
            with ann:
                yield
        finally:
            self.rows.append({"name": name, "start": t0,
                              "end": time.perf_counter(), **meta})

    def wrap(self, owner, attr: str, name: str) -> Callable:
        """Replace ``owner.attr`` by a pass-through that records a span
        around each call; returns a function that puts the original back."""
        orig = getattr(owner, attr)

        def spanned(*args, **kwargs):
            with self.span(name):
                return orig(*args, **kwargs)

        setattr(owner, attr, spanned)
        return lambda: setattr(owner, attr, orig)


# ---------------------------------------------------------------------------
# numbers
# ---------------------------------------------------------------------------


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile (``q`` in 0-100) of all values."""
    if not values:
        raise ValueError("percentile of nothing")
    vals = sorted(values)
    k = max(0, min(len(vals) - 1, int(-(-q * len(vals) // 100)) - 1))
    return vals[k]


# ---------------------------------------------------------------------------
# the result
# ---------------------------------------------------------------------------


def emit(result: Dict[str, Any], checks: Dict[str, Dict[str, float]]):
    """Each compared number beside its limit, as the last lines of stderr,
    then the result as the last line of stdout with the checks last."""
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    out = dict(result)
    out["checks"] = checks
    print(json.dumps(out), flush=True)


def window_spans(rows: List[Dict[str, Any]], prefix: str
                 ) -> List[Dict[str, Any]]:
    """The spans named ``prefix...`` that start inside the window."""
    win = [r for r in rows if r["name"] == "bench.window"]
    if not win:
        return []
    lo, hi = win[-1]["start"], win[-1]["end"]
    return [r for r in rows if r["name"].startswith(prefix)
            and lo <= r["start"] < hi]


def mean_span_s(rows: List[Dict[str, Any]], prefix: str) -> Optional[float]:
    got = [r["end"] - r["start"] for r in window_spans(rows, prefix)]
    return sum(got) / len(got) if got else None


def trace_spans(ctx, prefix: str):
    """The window's spans named ``prefix...`` as the trace recorded them
    (on the trace's own clock), each with the metadata the benchmark kept
    for a span of that name."""
    meta = {r["name"]: r for r in ctx.spans if r["name"].startswith(prefix)}
    lo, hi = ctx.trace_window
    for name, s, d in ctx.trace["spans"]:
        if name.startswith(prefix) and lo <= s < hi and name in meta:
            yield meta[name], s, s + d
