"""Amortized symbolic counting engine (the paper's amortization claim,
industrialized).

The paper gathers performance-relevant operation counts *symbolically
once* and re-evaluates them "in microseconds for any problem size".  The
repo's previous hot path re-ran ``jax.make_jaxpr`` plus a Python jaxpr
walk for every kernel at every size point — in the calibration battery
AND the serving path.  :class:`CountEngine` makes counting amortized and
observable:

* **content-addressed count cache** — concrete counts keyed by (callable
  signature, argument shapes/dtypes) or (generator ``code_sig``, kernel
  name, sizes), memoized in-process and persisted as JSON beside the
  :class:`~repro.profiles.MeasurementCache`
  (``MeasurementCache.count_store``).  Repeated predictions and warm
  battery gathers perform **zero traces and zero jaxpr walks** —
  ``hits``/``misses``/``trace_count`` make the claim assertable.
* **symbolic kernel families** — a generator declaring a
  :class:`~repro.core.uipick.FamilySpec` gets its
  :class:`~repro.core.counting.SymbolicCounts` reconstructed ONCE from
  the minimal probe grid (``degree+1`` traces per size variable), then
  whole size sweeps are filled by vectorized polynomial evaluation
  (:meth:`Poly.eval_batch` — batched Horner in flat numpy).  The
  reconstruction itself persists, so even the probe traces happen once
  per machine, ever.

When exact per-shape tracing is still used: kernels with data-dependent
or size-non-polynomial structure (no family declaration, e.g.
``mem_stream``'s strided pattern), and callables whose identity cannot
be established (no retrievable source, exotic closure state) — those
trace per shape, and the engine counts every such trace.
"""
from __future__ import annotations

import functools
import hashlib
import json
import re
import threading
import time
from dataclasses import dataclass
from fractions import Fraction
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro import spans
from repro.core.counting import (
    FeatureCounts,
    SymbolicCounts,
    count_fn,
    parametric_counts_from,
)
from repro.core.symbolic import ParametricCount, Poly
from repro.core.uipick import KernelFamily, MeasurementKernel, \
    source_signature

# bump when the persisted entry format changes; stale entries read as
# misses (never trusted) exactly like the measurement cache's discipline
# v2: pallas_call is opened by the static cost analyzer (grid-scaled body
# counts + block-spec HBM traffic) — v1 entries counted it as zero
COUNT_STORE_VERSION = 2

# memo of source hashes keyed by code object — getsource costs file IO,
# and serving loops sign the same callables over and over
_SRC_MEMO: Dict[Any, str] = {}


def _source_of(fn: Callable) -> str:
    code = getattr(fn, "__code__", None)
    if code is None:
        return source_signature(fn)
    sig = _SRC_MEMO.get(code)
    if sig is None:
        sig = source_signature(fn)
        _SRC_MEMO[code] = sig
    return sig


def _note(reasons: Optional[List[str]], why: str) -> None:
    if reasons is not None:
        reasons.append(why)


def _state_digest(value: Any, depth: int, seen: frozenset,
                  reasons: Optional[List[str]] = None) -> Optional[str]:
    """Stable digest of one piece of captured callable state (a closure
    cell, default argument, or bound ``self``), or None when no stable
    digest exists.  Conservative by design: an un-digestable value makes
    the whole callable unsignable (→ per-shape tracing), never a wrong
    cache key.  ``reasons`` (when given) collects WHY a digest failed —
    the raw material of :func:`signature_hazards`."""
    if depth > 3:
        _note(reasons, "captured state nests deeper than 3 levels")
        return None
    if isinstance(value, (int, float, bool, str, bytes, type(None))):
        return repr(value)
    if isinstance(value, np.dtype):
        # immutable with a canonical string form — a captured dtype (the
        # `dt = _dtype(dtype)` idiom of every UIPiCK builder) must not
        # make a kernel unsignable
        return f"dtype:{value.str}"
    if isinstance(value, (tuple, list)):
        parts = [_state_digest(v, depth + 1, seen, reasons) for v in value]
        if any(p is None for p in parts):
            return None
        return f"{type(value).__name__}({','.join(parts)})"  # type: ignore
    if isinstance(value, dict):
        parts = []
        for k in sorted(value, key=repr):
            dv = _state_digest(value[k], depth + 1, seen, reasons)
            if dv is None:
                return None
            parts.append(f"{k!r}:{dv}")
        return f"dict({','.join(parts)})"
    if type(value).__name__ == "module":
        # a referenced library module: identity by name — library-internal
        # edits are invisible, the same documented tradeoff as the
        # measurement cache's code_sig (bump versions for those)
        return f"module:{getattr(value, '__name__', '?')}"
    if hasattr(value, "shape") and hasattr(value, "dtype"):
        arr = np.asarray(value)
        if arr.size > 65536:
            # large captured arrays: hashing every byte on the serving hot
            # path defeats the point; shapes alone are not sound identity
            # (trace-time python branching may read values) — bail out
            _note(reasons,
                  f"captured array {arr.dtype}{list(arr.shape)} has "
                  f"{arr.size} elements (> 65536): hashing it per lookup "
                  f"would defeat the cache, shapes alone are unsound")
            return None
        return (f"{arr.dtype}[{','.join(map(str, arr.shape))}]:"
                f"{hashlib.sha256(np.ascontiguousarray(arr).tobytes()).hexdigest()[:12]}")
    if callable(value):
        if id(value) in seen:
            # cycle (e.g. a self-recursive closure captures itself): the
            # callable's own source already identifies it — a fixed marker
            # keeps the digest deterministic without recursing forever
            return "<cycle>"
        inner = _signature(value, depth + 1, seen | {id(value)}, reasons)
        return inner if inner else None
    _note(reasons,
          f"captured value of type {type(value).__name__!r} has no "
          f"stable content digest")
    return None


def _signature(fn: Callable, depth: int, seen: frozenset,
               reasons: Optional[List[str]] = None) -> str:
    # transparent wrappers first: a partial signs as its target plus a
    # digest of the bound arguments, and a sourceless wrapper honoring the
    # __wrapped__ protocol (jit's PjitFunction, functools.wraps) signs as
    # what it wraps — neither changes what the traced jaxpr counts
    if isinstance(fn, functools.partial):
        if id(fn.func) in seen:
            return ""
        inner = _signature(fn.func, depth, seen | {id(fn.func)}, reasons)
        if not inner:
            return ""
        bound = _state_digest([list(fn.args), dict(fn.keywords)],
                              depth, seen, reasons)
        if bound is None:
            return ""
        return f"partial({inner};{bound})"
    src = _source_of(fn)
    if not src:
        wrapped = getattr(fn, "__wrapped__", None)
        if wrapped is not None and id(wrapped) not in seen:
            inner = _signature(wrapped, depth, seen | {id(wrapped)},
                               reasons)
            return f"wrapped({inner})" if inner else ""
        _note(reasons,
              f"callable {getattr(fn, '__name__', fn)!r} has no "
              f"retrievable source (REPL/exec or builtin)")
        return ""
    parts: List[str] = [src]
    # a bound method's behavior depends on instance state: digest self and
    # sign the underlying function (whose closure/defaults are then seen)
    inner = getattr(fn, "__func__", None)
    if inner is not None:
        self_digest = _state_digest(getattr(fn, "__self__", None),
                                    depth, seen, reasons)
        if self_digest is None:
            return ""
        parts.append(f"self:{self_digest}")
        fn = inner
    kwdefaults = getattr(fn, "__kwdefaults__", None) or {}
    state = []
    for cell in getattr(fn, "__closure__", None) or ():
        try:
            state.append(cell.cell_contents)
        except ValueError:       # still-empty cell: no stable identity
            _note(reasons, "closure cell is still empty (recursive "
                           "definition not yet bound)")
            return ""
    state += list(getattr(fn, "__defaults__", None) or ())
    state += [v for _, v in sorted(kwdefaults.items())]
    for value in state:
        digest = _state_digest(value, depth, seen, reasons)
        if digest is None:
            return ""
        parts.append(digest)
    # module-level globals the body references (co_names, including the
    # names nested code objects reference) are captured state too: editing
    # a referenced helper must change the signature, or a warm store would
    # serve the OLD helper's counts.  Names not in __globals__ (builtins,
    # attribute names) don't bind module state.
    code = getattr(fn, "__code__", None)
    fn_globals = getattr(fn, "__globals__", None)
    if code is not None and fn_globals is not None:
        for name in sorted(_referenced_names(code)):
            if name not in fn_globals:
                continue
            digest = _state_digest(fn_globals[name], depth, seen, reasons)
            if digest is None:
                _note(reasons, f"(the undigestable value above is the "
                               f"module-level global {name!r})")
                return ""
            parts.append(f"g:{name}={digest}")
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:16]


def _referenced_names(code) -> set:
    """co_names of a code object and every nested code object it carries
    in co_consts (inner defs/lambdas reference globals through their own
    code, not the enclosing one)."""
    names = set(code.co_names)
    for const in code.co_consts:
        if hasattr(const, "co_names"):
            names |= _referenced_names(const)
    return names


def callable_signature(fn: Callable) -> str:
    """Content identity of a callable for count caching: source hash plus
    a digest of its captured state (closure cells, positional AND
    keyword-only defaults, bound-method ``self`` — each changes what the
    traced jaxpr looks like).  Returns ``""`` when no sound identity
    exists; such callables are traced per shape."""
    return _signature(fn, 0, frozenset({id(fn)}))


def signature_hazards(fn: Callable) -> List[str]:
    """Why ``fn`` signs as ``""`` — one human-readable reason per
    undigestable piece of captured state, empty when the callable IS
    signable.  The same walk as :func:`callable_signature` (same
    conservative rules), run once with a reason collector: the static
    cache-signature hazard detector (``repro.analysis.sighazards``) turns
    these into diagnostics instead of letting the ``""`` signature
    silently defeat :class:`CountEngine` dedup at serving time."""
    reasons: List[str] = []
    sig = _signature(fn, 0, frozenset({id(fn)}), reasons)
    if sig:
        return []
    return reasons or ["callable has no stable content identity"]


def args_signature(args: Sequence[Any]) -> str:
    """Canonical shapes/dtypes signature of example arguments (counts
    depend on abstract shapes, plus the repr of python scalars — concrete
    values can steer trace-time branching)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tuple(args))
    parts = []
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            parts.append(
                f"{leaf.dtype}[{','.join(str(d) for d in leaf.shape)}]")
        else:
            parts.append(f"py:{type(leaf).__name__}:{leaf!r}")
    return f"{treedef}|{';'.join(parts)}"


# ---------------------------------------------------------------------------
# polynomial (de)serialization for persisted symbolic families
# ---------------------------------------------------------------------------


def _poly_to_json(p: Poly) -> List[Any]:
    return [[[[v, e] for v, e in mono], c.numerator, c.denominator]
            for mono, c in sorted(p.terms.items())]


def _poly_from_json(terms: Any) -> Poly:
    out = {}
    for mono, num, den in terms:
        key = tuple((str(v), int(e)) for v, e in mono)
        out[key] = Fraction(int(num), int(den))
    return Poly(out)


def _symbolic_to_json(sym: SymbolicCounts) -> Dict[str, Any]:
    return {
        "assumptions": list(sym.assumptions),
        "counts": {fid: _poly_to_json(pc.poly)
                   for fid, pc in sorted(sym.counts.items())},
    }


def _symbolic_from_json(payload: Dict[str, Any]) -> SymbolicCounts:
    assumptions = tuple(str(a) for a in payload["assumptions"])
    counts = {str(fid): ParametricCount(_poly_from_json(terms), assumptions)
              for fid, terms in payload["counts"].items()}
    return SymbolicCounts(counts, assumptions)


# ---------------------------------------------------------------------------
# count-store eviction
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CountStoreGCStats:
    """Outcome of one :meth:`CountEngine.gc` sweep, mirroring the
    measurement cache's :class:`~repro.profiles.cache.GCStats` shape.
    Counts are machine-independent, so there is no foreign-fingerprint
    class; an entry whose embedded key disagrees with its filename counts
    as corrupt (hand-edited or mis-copied files are never trusted)."""

    kept: int = 0
    dropped_old: int = 0
    dropped_corrupt: int = 0
    dropped_schema: int = 0

    @property
    def dropped(self) -> int:
        return self.dropped_old + self.dropped_corrupt + self.dropped_schema


# count-store entries are named by the full 64-hex SHA-256 of their key —
# anything else under counts/ or families/ is not ours to delete
_STORE_ENTRY_NAME = re.compile(r"[0-9a-f]{64}\.json")


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


class _TimedLock:
    """A re-entrant lock that keeps how long its holder's outermost
    acquire waited (``wait_s``), for the first ``count.trace`` span under
    that acquire.  The two fields are only touched by the thread that
    holds the lock."""

    __slots__ = ("_lock", "_depth", "wait_s")

    def __init__(self):
        self._lock = threading.RLock()
        self._depth = 0
        self.wait_s = 0.0

    def __enter__(self) -> "_TimedLock":
        t0 = time.perf_counter()
        self._lock.acquire()
        if not self._depth:
            self.wait_s = time.perf_counter() - t0
        self._depth += 1
        return self

    def __exit__(self, *exc) -> None:
        self._depth -= 1
        self._lock.release()


class CountEngine:
    """Amortized feature counting with an observable cost model.

    ``store`` is a directory for the persistent tier (typically
    ``MeasurementCache.count_store`` — beside the measurement entries);
    ``None`` keeps the engine in-process only.  Counters:

    * ``trace_count`` — actual ``jax.make_jaxpr`` + jaxpr-walk passes
      performed (symbolic probe traces included).  THE number the
      zero-trace warm-path guarantees are asserted against.
    * ``hits``/``misses`` — count-cache lookups (concrete keys and
      symbolic families alike; a family reconstruction is one miss even
      though it probes several grid points).

    **Thread safety.**  The engine is shared by every request thread of a
    serving daemon, so all public lookups (``counts_for``,
    ``counts_of_callable``, ``counts_batch``, ``symbolic``) and the
    ``stats()`` snapshot serialize on one re-entrant lock: cache mutation,
    counter updates, and persisted-store writes are atomic with the lookup
    that caused them (two threads racing a cold kernel perform exactly ONE
    trace, and ``hits + misses`` always equals the number of lookups).
    The lock is allocated once at construction — the single-threaded warm
    fast path pays one uncontended acquire, no per-lookup allocation.
    """

    def __init__(self, store: Any = None):
        self.store = Path(store).expanduser() if store is not None else None
        self.hits = 0
        self.misses = 0
        self.trace_count = 0
        self._counts: Dict[str, FeatureCounts] = {}
        self._families: Dict[str, SymbolicCounts] = {}
        # re-entrant: counts_batch holds it while delegating to counts_for
        # and symbolic.  Held across cold traces on purpose — serializing
        # the trace is what guarantees one trace per key under contention.
        self._lock = _TimedLock()

    # -- tracing seam (every make_jaxpr in the engine goes through here) --
    def _trace(self, fn: Callable, args: Sequence[Any]) -> FeatureCounts:
        """Called with the lock held; the span ``count.trace`` carries how
        long this thread waited for it (``lock_wait_s``), on the first
        trace under one acquire only, so that sums count each wait once."""
        self.trace_count += 1
        wait, self._lock.wait_s = self._lock.wait_s, 0.0
        with spans.span("count.trace", lock_wait_s=wait):
            return count_fn(fn, *args)

    # -- concrete counts ---------------------------------------------------
    def counts_for(self, kernel: MeasurementKernel, *,
                   sig: Optional[str] = None) -> FeatureCounts:
        """One measurement kernel's counts, through the cache.  Kernels
        carrying a symbolic family evaluate their family polynomial (zero
        traces once the family is reconstructed — any size, including
        sizes never seen before); others are keyed by (generator code
        signature, kernel name, sizes) — the same identity contract as
        the measurement cache, minus the device-specific parts: counts
        are machine-independent.  ``sig`` lets callers that already
        computed the content signature (dedup keys) pass it down instead
        of paying the state walk twice per item."""
        fam = kernel.family
        if fam is not None and set(fam.var_degrees) == set(kernel.sizes):
            return self.counts_batch([kernel])[0]
        if sig is None:
            sig = kernel.code_sig or callable_signature(kernel.fn)
        if not sig:
            # no content identity: (name, sizes) alone could collide two
            # different hand-built kernels — trace exactly, every time
            with self._lock:
                self.misses += 1
                return self._trace(kernel.fn, kernel.make_args())
        key = self._digest({
            "kind": "kernel", "sig": sig, "name": kernel.name,
            "sizes": {k: int(v) for k, v in sorted(kernel.sizes.items())},
        })
        with self._lock:
            return self._concrete(
                key, persist=True,
                build=lambda: (kernel.fn, kernel.make_args()))

    def counts_of_callable(self, fn: Callable, args: Sequence[Any] = (),
                           *, sig: Optional[str] = None) -> FeatureCounts:
        """Counts of a bare callable at example-argument shapes — the
        serving path for ad-hoc ``predict`` items.  ``sig`` as in
        :meth:`counts_for`."""
        if sig is None:
            sig = callable_signature(fn)
        if not sig:
            # no stable identity: always an exact per-shape trace
            with self._lock:
                self.misses += 1
                return self._trace(fn, args)
        key = self._digest({"kind": "fn", "sig": sig,
                            "args": args_signature(args)})
        with self._lock:
            return self._concrete(key, persist=True,
                                  build=lambda: (fn, args))

    def _concrete(self, key: str, persist: bool,
                  build: Callable[[], Tuple[Callable, Sequence[Any]]]
                  ) -> FeatureCounts:
        found = self._counts.get(key)
        if found is not None:
            self.hits += 1
            return found
        if persist and self.store is not None:
            loaded = self._load_json(self._counts_path(key))
            if loaded is not None and loaded.get("key") == key \
                    and isinstance(loaded.get("counts"), dict):
                fc = FeatureCounts({str(k): float(v)
                                    for k, v in loaded["counts"].items()})
                self._counts[key] = fc
                self.hits += 1
                return fc
        self.misses += 1
        fn, args = build()
        fc = self._trace(fn, args)
        self._counts[key] = fc
        if persist and self.store is not None:
            self._save_json(self._counts_path(key), {
                "version": COUNT_STORE_VERSION, "key": key,
                "counts": {k: float(v) for k, v in sorted(fc.items())},
            })
        return fc

    # -- symbolic families -------------------------------------------------
    def symbolic(self, family: KernelFamily) -> SymbolicCounts:
        """The family's symbolic counts — reconstructed from the minimal
        probe grid on first sight, then cached in-process and persisted.
        Probe traces are the ONLY traces a symbolic family ever costs."""
        key = self._digest({"kind": "family", "family": family.key,
                            "version": COUNT_STORE_VERSION})
        with self._lock:
            sym = self._families.get(key)
            if sym is not None:
                self.hits += 1
                return sym
            if self.store is not None:
                loaded = self._load_json(self._family_path(key))
                if loaded is not None and loaded.get("key") == key \
                        and isinstance(loaded.get("counts"), dict):
                    try:
                        sym = _symbolic_from_json(loaded)
                    except (KeyError, TypeError, ValueError,
                            ZeroDivisionError):
                        sym = None      # corrupt entry reads as a miss
                    if sym is not None:
                        self._families[key] = sym
                        self.hits += 1
                        return sym
            self.misses += 1

            def probe(**sizes) -> FeatureCounts:
                k = family.build(**sizes)
                return self._trace(k.fn, k.make_args())

            sym = parametric_counts_from(probe, family.var_degrees,
                                         base=family.base,
                                         scale=family.scale)
            self._families[key] = sym
            if self.store is not None:
                payload = _symbolic_to_json(sym)
                payload.update(version=COUNT_STORE_VERSION, key=key,
                               family=family.key)
                self._save_json(self._family_path(key), payload)
            return sym

    def counts_batch(self, kernels: Sequence[MeasurementKernel]
                     ) -> List[FeatureCounts]:
        """Counts for a whole battery: kernels carrying the same symbolic
        family share ONE reconstruction and get their rows from vectorized
        polynomial evaluation; the rest go through the concrete cache."""
        with self._lock:
            out: List[Optional[FeatureCounts]] = [None] * len(kernels)
            groups: Dict[str, Tuple[KernelFamily, List[int]]] = {}
            for i, k in enumerate(kernels):
                fam = k.family
                if fam is not None and set(fam.var_degrees) == set(k.sizes):
                    groups.setdefault(fam.key, (fam, []))[1].append(i)
                else:
                    out[i] = self.counts_for(k)
            for fam, idxs in groups.values():
                sym = self.symbolic(fam)
                env = {v: np.asarray([kernels[i].sizes[v] for i in idxs],
                                     np.float64)
                       for v in fam.var_degrees}
                matrix = sym.at_batch(**env)
                for j, i in enumerate(idxs):
                    out[i] = FeatureCounts(
                        {fid: float(col[j]) for fid, col in matrix.items()
                         if col[j] != 0.0})
            return [fc if fc is not None else FeatureCounts()
                    for fc in out]

    # -- persistence --------------------------------------------------------
    def _digest(self, payload: Dict[str, Any]) -> str:
        return hashlib.sha256(
            json.dumps(payload, sort_keys=True).encode()).hexdigest()

    def _counts_path(self, key: str) -> Path:
        assert self.store is not None
        return self.store / "counts" / f"{key}.json"

    def _family_path(self, key: str) -> Path:
        assert self.store is not None
        return self.store / "families" / f"{key}.json"

    @staticmethod
    def _load_json(path: Path) -> Optional[Dict[str, Any]]:
        try:
            payload = json.loads(path.read_text())
        except (OSError, ValueError):
            return None
        if not isinstance(payload, dict) \
                or payload.get("version") != COUNT_STORE_VERSION:
            return None
        return payload

    @staticmethod
    def _save_json(path: Path, payload: Dict[str, Any]) -> None:
        from repro.checkpoint.manager import atomic_write_json

        path.parent.mkdir(parents=True, exist_ok=True)
        atomic_write_json(path, payload)

    # -- eviction ------------------------------------------------------------
    def gc(self, *, max_age: Optional[float] = None,
           now: Optional[float] = None) -> CountStoreGCStats:
        """Evict stale persisted counts (the ROADMAP count-store GC item),
        mirroring :meth:`~repro.profiles.cache.MeasurementCache.gc`.

        Sweeps both tiers (``counts/`` and ``families/``) and drops, in
        order of precedence: corrupt files (unparseable, not entry-shaped,
        or embedded key ≠ filename stem — a mis-copied or hand-edited file
        can never match a lookup), entries written under a different
        ``COUNT_STORE_VERSION`` (permanently dead weight), and entries
        older than ``max_age`` seconds by file mtime.  Files not named by
        a 64-hex digest are never ours to touch.  In-process memos are
        untouched: GC governs the persistent tier only.
        """
        if now is None:
            now = time.time()
        kept = old = corrupt = stale_schema = 0
        if self.store is None:
            return CountStoreGCStats()
        for sub in ("counts", "families"):
            tier = self.store / sub
            if not tier.is_dir():
                continue
            for path in sorted(tier.glob("*.json")):
                if not _STORE_ENTRY_NAME.fullmatch(path.name):
                    continue
                try:
                    mtime = path.stat().st_mtime
                except OSError:
                    continue    # vanished under a concurrent sweep
                try:
                    payload = json.loads(path.read_text())
                    if not isinstance(payload, dict) \
                            or payload.get("key") != path.stem \
                            or not isinstance(payload.get("counts"), dict):
                        raise ValueError("not a count-store entry")
                except (OSError, ValueError):
                    path.unlink(missing_ok=True)
                    corrupt += 1
                    continue
                if payload.get("version") != COUNT_STORE_VERSION:
                    path.unlink(missing_ok=True)
                    stale_schema += 1
                    continue
                if max_age is not None and now - mtime > max_age:
                    path.unlink(missing_ok=True)
                    old += 1
                    continue
                kept += 1
        return CountStoreGCStats(kept=kept, dropped_old=old,
                                 dropped_corrupt=corrupt,
                                 dropped_schema=stale_schema)

    # -- observability -------------------------------------------------------
    def stats(self) -> Dict[str, int]:
        """A *consistent* counter snapshot: taken under the engine lock so
        a concurrent lookup can never be observed half-applied (e.g. a
        miss counted whose trace has not landed in ``trace_count`` yet)."""
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "trace_count": self.trace_count,
                    "families": len(self._families)}
