"""Spans at the layer boundaries of the calibrate, price and serve paths.

``with span("measure.load", kernel=name):`` does two things:

* it enters ``jax.profiler.TraceAnnotation("repro.measure.load")``, so
  the span lands on the host plane of any profiler trace that is
  running, on the same clock as the device's operations (the view in
  Perfetto or TensorBoard);
* it records ``Span(id, parent, root, name, t0_ns, t1_ns, thread,
  attrs)`` in a bounded in-memory ring, timed with
  ``time.perf_counter_ns``.  ``parent`` and ``root`` come from a stack
  kept per thread: ``root`` is the id of the outermost open span, one
  calibration or one coalesced batch, and ``parent`` is 0 for a root.

Counts ride as attrs of the span at their boundary (``rows=``,
``traces=``); ``with span(...) as s: s.attrs["hits"] = n`` sets one
after the work, and ``add("reused")`` counts one into the innermost
open span from code below the layer that opened it.  JAX's compile
events add ``compiles`` (backend compiles, persistent-cache reads
included), ``cache_hits`` (those read from the persistent cache),
``cache_misses`` (those written to it) and ``compile_s`` to the
innermost open span of the thread they fire on, so a compile is charged
to the step that caused it; a compile on a thread with no open span is
counted in :func:`unowned`.

The recorder is always on, so it stays cheap: a few microseconds a
span, no span inside a loop of timed calls, and no formatting of attrs
while recording.  :func:`between` reads finished spans, :func:`totals`
per name the count, the seconds and the sum of each numeric attr since
the process started (the serving daemon's ``GET /stats``).
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Dict, Iterable, List, NamedTuple

import jax

#: profiler annotations carry this before the span's name
ANNOTATION_PREFIX = "repro."
#: finished spans kept: about a hundred windows of calibrations
RING = 1 << 16

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_COMPILE_ATTRS = ("compiles", "cache_hits", "cache_misses", "compile_s")


class Span(NamedTuple):
    """One finished span; times are ``perf_counter_ns``."""

    id: int
    parent: int            # 0 for a root
    root: int
    name: str
    t0_ns: int
    t1_ns: int
    thread: int
    attrs: Dict[str, Any]

    @property
    def seconds(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9


class _Open:
    """A span while it is open; ``attrs`` may be added to until it
    closes."""

    __slots__ = ("_rec", "name", "attrs", "id", "parent", "root", "t0_ns",
                 "_ann")

    def __init__(self, rec: "Recorder", name: str, attrs: Dict[str, Any]):
        self._rec = rec
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "_Open":
        rec = self._rec
        stack = rec._stack()
        self.id = next(rec._ids)
        if stack:
            top = stack[-1]
            self.parent, self.root = top.id, top.root
        else:
            self.parent, self.root = 0, self.id
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(ANNOTATION_PREFIX
                                                 + self.name)
        self._ann.__enter__()
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        t1 = time.perf_counter_ns()
        self._ann.__exit__(exc_type, exc, tb)
        rec = self._rec
        rec._stack().pop()
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        rec._finish(Span(self.id, self.parent, self.root, self.name,
                         self.t0_ns, t1, threading.get_ident(), self.attrs))


class Recorder:
    """A ring of finished spans, per-name totals, and the open spans of
    each thread.  The module's functions use one recorder for the
    process; tests make their own."""

    def __init__(self, maxlen: int = RING):
        self._ring: collections.deque = collections.deque(maxlen=maxlen)
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._lock = threading.Lock()
        # name -> [count, ns, {attr: sum}]
        self._totals: Dict[str, List] = {}
        # compile attrs of compiles on a thread with no open span
        self._unowned: Dict[str, float] = dict.fromkeys(_COMPILE_ATTRS, 0)

    def _stack(self) -> List[_Open]:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def _finish(self, s: Span) -> None:
        with self._lock:
            self._ring.append(s)
            t = self._totals.get(s.name)
            if t is None:
                t = self._totals[s.name] = [0, 0, {}]
            t[0] += 1
            t[1] += s.t1_ns - s.t0_ns
            sums = t[2]
            for k, v in s.attrs.items():
                if isinstance(v, (int, float)):
                    sums[k] = sums.get(k, 0) + v

    def span(self, name: str, **attrs: Any) -> _Open:
        return _Open(self, name, attrs)

    def innermost(self):
        """This thread's innermost open span, or None."""
        stack = self._stack()
        return stack[-1] if stack else None

    def add(self, key: str, amount: float = 1) -> None:
        """Add ``amount`` to attr ``key`` of this thread's innermost open
        span, for a count made below the layer that opened it; nothing
        where no span is open."""
        top = self.innermost()
        if top is not None:
            top.attrs[key] = top.attrs.get(key, 0) + amount

    def between(self, t0_ns: int, t1_ns: int) -> List[Span]:
        """Finished spans that start in ``[t0_ns, t1_ns)``, by start."""
        with self._lock:
            got = [s for s in self._ring if t0_ns <= s.t0_ns < t1_ns]
        got.sort(key=lambda s: s.t0_ns)
        return got

    def totals(self) -> Dict[str, Dict[str, float]]:
        """Per name, since the start: spans finished (``count``), their
        ``seconds``, and each numeric attr summed under its own name (a
        flag counts the spans that set it)."""
        with self._lock:
            return {n: {"count": c, "seconds": ns / 1e9, **sums}
                    for n, (c, ns, sums) in sorted(self._totals.items())}

    def unowned(self) -> Dict[str, float]:
        """The compile attrs of compiles that fired on a thread with no
        open span, since the start."""
        with self._lock:
            return dict(self._unowned)

    # -- JAX's compile events, charged to the innermost open span --------
    def _charge(self, key: str, amount: float) -> None:
        top = self.innermost()
        if top is not None:
            top.attrs[key] = top.attrs.get(key, 0) + amount
        else:
            with self._lock:
                self._unowned[key] += amount

    def on_duration(self, event: str, duration: float, **_) -> None:
        if event == _BACKEND_COMPILE:
            self._charge("compiles", 1)
            self._charge("compile_s", duration)

    def on_event(self, event: str, **_) -> None:
        if event == _CACHE_HIT:
            self._charge("cache_hits", 1)
        elif event == _CACHE_MISS:
            self._charge("cache_misses", 1)

    def listen(self) -> None:
        """Register for JAX's compile events; once per recorder."""
        jax.monitoring.register_event_duration_secs_listener(
            self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)


def self_ns(parent: Span, spans: Iterable[Span]) -> int:
    """``parent``'s self time: its duration less the union of its
    children's intervals (clipped to it), children that overlap counted
    once."""
    cover, end = 0, parent.t0_ns
    for s0, s1 in sorted((max(s.t0_ns, parent.t0_ns),
                          min(s.t1_ns, parent.t1_ns))
                         for s in spans if s.parent == parent.id):
        s0 = max(s0, end)
        if s1 > s0:
            cover += s1 - s0
            end = s1
    return parent.t1_ns - parent.t0_ns - cover


_RECORDER = Recorder()
_RECORDER.listen()

span = _RECORDER.span
add = _RECORDER.add
between = _RECORDER.between
totals = _RECORDER.totals
unowned = _RECORDER.unowned
