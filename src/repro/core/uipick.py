"""UIPiCK — a parameterized collection of measurement-kernel generators
(paper §7.1), re-targeted from OpenCL to JAX.

Each *generator* owns
  * a set of **generator filter tags** (single values, e.g. ``matmul_sq``),
  * an **argument space** — allowed values per argument; one kernel is
    produced per element of the Cartesian product of allowed values,
and the collection filters generators/variants from user-provided tags
under one of the paper's four match conditions.

Measurement kernels are ordinary jit-able JAX callables with concrete
argument builders, so they can be (a) *timed* on the host device for
black-box calibration, and (b) *counted* by ``repro.core.counting`` for
feature extraction — the same dual use as the paper's OpenCL kernels.
The Pallas twins of the hot kernels live in ``repro.kernels``.
"""
from __future__ import annotations

import enum
import hashlib
import inspect
import itertools
import json
import time
import types
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, FrozenSet, Iterable, List, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core.counting import FeatureCounts, count_fn
from repro.core.model import FeatureTable
from repro.deprecation import warn_once


def source_signature(fn: Callable) -> str:
    """Cheap source-level identity of a callable: SHA-256 of its
    ``inspect.getsource`` text, truncated.  Computed once at generator
    registration — NO tracing, no jaxpr — so warm cache runs stay free,
    yet editing a generator's body changes the signature and naturally
    invalidates that generator's measurement-cache entries.  Callables
    without retrievable source (REPL/exec) sign as ``""``."""
    try:
        src = inspect.getsource(fn)
    except (OSError, TypeError):
        return ""
    return hashlib.sha256(src.encode()).hexdigest()[:16]


class MatchCondition(enum.Enum):
    IDENTICAL = 1   # generator tag set == user tags
    SUBSET = 2      # generator tag set ⊆ user tags
    SUPERSET = 3    # generator tag set ⊇ user tags (paper default)
    INTERSECT = 4   # non-empty intersection


@dataclass(frozen=True)
class TimingStats:
    """One timing measurement with its wall-clock noise: the median drives
    calibration (robust to scheduler spikes), ``std``/``min`` are the noise
    metadata persisted per cache entry and surfaced in fit diagnostics to
    drive re-measurement heuristics (ROADMAP follow-up)."""

    median: float
    std: Optional[float] = None
    min: Optional[float] = None

    @classmethod
    def coerce(cls, value: "TimerResult") -> "TimingStats":
        """Accept either a bare seconds float (legacy/injected timers) or a
        full :class:`TimingStats`."""
        if isinstance(value, TimingStats):
            return value
        return cls(median=float(value))

    def to_dict(self) -> Dict[str, float]:
        d = {"median": float(self.median)}
        if self.std is not None:
            d["std"] = float(self.std)
        if self.min is not None:
            d["min"] = float(self.min)
        return d


TimerResult = Union[float, TimingStats]


@dataclass(frozen=True)
class FamilySpec:
    """A generator's declaration that its kernels form a *symbolic family*:
    operation counts are polynomial in the declared size variables with the
    declared degrees, so the count engine can reconstruct the family's
    :class:`~repro.core.counting.SymbolicCounts` from a minimal probe grid
    once and evaluate the whole size sweep by vectorized polynomial
    evaluation — zero traces per battery member.

    ``applies(**fixed)`` gates the declaration per fixed (non-size)
    argument combination (e.g. ``mem_stream``'s ``strided`` pattern shapes
    as ``isqrt(n)²`` — not polynomial in ``n`` — and opts out);
    ``probe(**fixed)`` overrides the probe-grid geometry (e.g. tile-aligned
    probe sizes for blocked matmuls).
    """

    var_degrees: Mapping[str, int]
    base: int = 16
    scale: int = 16
    applies: Optional[Callable[..., bool]] = None
    probe: Optional[Callable[..., Tuple[int, int]]] = None


@dataclass
class KernelFamily:
    """One concrete symbolic family riding on a measurement kernel: a
    content-stable ``key`` (generator source signature + fixed args +
    degrees + probe geometry) and a ``build(**sizes)`` hook rebuilding the
    family member at arbitrary probe sizes.  Kernels sharing a family key
    share one symbolic reconstruction in the count engine."""

    key: str
    build: Callable[..., "MeasurementKernel"]
    var_degrees: Dict[str, int]
    base: int = 16
    scale: int = 16


@dataclass
class MeasurementKernel:
    name: str
    fn: Callable
    make_args: Callable[[], tuple]
    tags: Dict[str, Any]
    sizes: Dict[str, int] = field(default_factory=dict)
    # source-level identity of the generator body that built this kernel
    # (see :func:`source_signature`); part of the measurement-cache key so
    # editing a generator invalidates its cached timings without a global
    # schema bump.  "" for hand-built kernels (tests, ad-hoc measurement).
    code_sig: str = ""
    # the symbolic family this kernel belongs to (attached by
    # Generator.variants when the generator declares a FamilySpec); None
    # for hand-built kernels and non-polynomial argument combinations
    family: Optional[KernelFamily] = None

    _counts: Optional[FeatureCounts] = None
    _jitted: Optional[Callable] = None

    def counts(self) -> FeatureCounts:
        if self._counts is None:
            self._counts = count_fn(self.fn, *self.make_args())
        return self._counts

    def jitted(self) -> Callable:
        """The jit-compiled kernel, traced once and cached on the kernel so
        repeated timings don't pay re-tracing."""
        if self._jitted is None:
            self._jitted = jax.jit(self.fn)
        return self._jitted

    def time(self, *, trials: int = 20, warmup: int = 3) -> float:
        """Median wall-clock seconds per call on the host device.

        ``warmup=0`` skips the warmup entirely (the first trial then pays
        compilation — useful for cold-start measurement).
        """
        return self.time_stats(trials=trials, warmup=warmup).median

    def time_stats(self, *, trials: int = 20, warmup: int = 3
                   ) -> TimingStats:
        """One timing pass reported with its spread (median/std/min).

        Spans: ``measure.args`` builds the arguments; ``measure.load``
        gets the program (trace, lower, and compile or read it from the
        persistent cache) and makes the first warm-up call, blocked until
        ready; ``measure.time`` makes the other warm-up calls and the
        timed trials."""
        with spans.span("measure.args"):
            args = self.make_args()
        out = None
        with spans.span("measure.load"):
            jf = self.jitted()
            if warmup:
                jax.block_until_ready(jf(*args))
        with spans.span("measure.time", trials=trials):
            for _ in range(warmup - 1):
                out = jf(*args)
            if out is not None:
                jax.block_until_ready(out)
            ts = []
            for _ in range(trials):
                t0 = time.perf_counter()
                jax.block_until_ready(jf(*args))
                ts.append(time.perf_counter() - t0)
        return TimingStats(median=float(np.median(ts)),
                           std=float(np.std(ts)), min=float(np.min(ts)))


@dataclass
class Generator:
    name: str
    gen_tags: FrozenSet[str]
    arg_space: Dict[str, Tuple[Any, ...]]
    build: Callable[..., MeasurementKernel]
    code_sig: str = ""
    # symbolic-family declaration: counts of this generator's kernels are
    # polynomial (with these degrees) in the size variables; None opts the
    # generator out of symbolic counting entirely
    family: Optional[FamilySpec] = None

    def __post_init__(self):
        # signature of the builder source (which lexically contains the
        # kernel bodies it closes over) — computed ONCE at registration
        if not self.code_sig:
            self.code_sig = source_signature(self.build)

    def _family_of(self, kw: Mapping[str, Any]) -> Optional[KernelFamily]:
        spec = self.family
        if spec is None:
            return None
        fixed = {a: v for a, v in kw.items() if a not in spec.var_degrees}
        if spec.applies is not None and not spec.applies(**fixed):
            return None
        base, scale = (spec.probe(**fixed) if spec.probe is not None
                       else (spec.base, spec.scale))
        key = json.dumps({
            "gen": self.name,
            "code": self.code_sig,
            "fixed": {a: repr(v) for a, v in sorted(fixed.items())},
            "degrees": {v: int(d) for v, d
                        in sorted(spec.var_degrees.items())},
            "base": int(base), "scale": int(scale),
        }, sort_keys=True)
        build = self.build

        def build_at(**sizes) -> MeasurementKernel:
            return build(**{**fixed, **sizes})

        return KernelFamily(key=key, build=build_at,
                            var_degrees=dict(spec.var_degrees),
                            base=int(base), scale=int(scale))

    def variants(self, constraints: Mapping[str, Tuple[Any, ...]]
                 ) -> Iterable[MeasurementKernel]:
        space = {}
        for arg, allowed in self.arg_space.items():
            if arg in constraints:
                chosen = tuple(v for v in constraints[arg] if v in allowed)
                if not chosen:
                    return  # constraint excludes this generator entirely
                space[arg] = chosen
            else:
                space[arg] = allowed
        names = sorted(space)
        families: Dict[Tuple, Optional[KernelFamily]] = {}
        warned: set = set()
        for combo in itertools.product(*(space[n] for n in names)):
            kw = dict(zip(names, combo))
            try:
                kernel = self.build(**kw)
            except _SkipVariant:
                continue
            if not kernel.code_sig:
                kernel.code_sig = self.code_sig
            if isinstance(kernel.fn, types.FunctionType):
                # its program then reads as the generator in a device
                # trace (``jit_matmul_sq``), not as ``fn``, the name every
                # generator gives its callable
                kernel.fn.__name__ = self.name
            if self.family is not None and kernel.family is None:
                fixed_key = tuple(sorted(
                    (a, v) for a, v in kw.items()
                    if a not in self.family.var_degrees))
                if fixed_key not in families:
                    families[fixed_key] = self._family_of(kw)
                kernel.family = families[fixed_key]
            fam = kernel.family
            if fam is not None and fam.scale > 1:
                for var in fam.var_degrees:
                    size = int(kernel.sizes.get(var, 0))
                    if size % fam.scale and (var, size) not in warned:
                        warned.add((var, size))
                        warnings.warn(
                            f"generator {self.name!r}: requested size "
                            f"{var}={size} violates the symbolic family's "
                            f"probe-lattice assumption "
                            f"{var} % {fam.scale} == 0 — the count "
                            f"polynomial extrapolates off the verified "
                            f"lattice", LatticeAssumptionWarning,
                            stacklevel=2)
            yield kernel


class _SkipVariant(Exception):
    """Raised by builders for incoherent argument combinations."""


class LatticeAssumptionWarning(UserWarning):
    """A requested kernel size violates its symbolic family's probe-lattice
    divisibility assumption (``var % scale == 0``).  The family polynomial
    is still evaluated at that size — counts of the built-in families are
    genuinely polynomial everywhere — but the reconstruction was only
    *verified* on the lattice, so off-lattice sizes are extrapolation the
    probe grid never witnessed.  Emitted by :meth:`Generator.variants`
    (and surfaced as a ``probe-lattice-divisibility`` diagnostic by
    ``repro.analysis``)."""


def _parse_value(s: str) -> Any:
    if s in ("True", "False"):
        return s == "True"
    try:
        return int(s)
    except ValueError:
        pass
    try:
        return float(s)
    except ValueError:
        return s


def parse_filter_tags(filter_tags: Sequence[str]
                      ) -> Tuple[FrozenSet[str], Dict[str, Tuple[Any, ...]]]:
    gen_tags: set = set()
    variant: Dict[str, Tuple[Any, ...]] = {}
    for t in filter_tags:
        if ":" in t:
            arg, vals = t.split(":", 1)
            variant[arg] = tuple(_parse_value(v) for v in vals.split(","))
        else:
            gen_tags.add(t)
    return frozenset(gen_tags), variant


class KernelCollection:
    def __init__(self, generators: Sequence[Generator]):
        self.generators = list(generators)

    def generate_kernels(
        self,
        filter_tags: Sequence[str],
        generator_match_cond: MatchCondition = MatchCondition.SUPERSET,
    ) -> List[MeasurementKernel]:
        user_tags, constraints = parse_filter_tags(filter_tags)
        out: List[MeasurementKernel] = []
        for g in self.generators:
            gt = g.gen_tags
            if generator_match_cond is MatchCondition.IDENTICAL:
                ok = gt == user_tags
            elif generator_match_cond is MatchCondition.SUBSET:
                ok = gt <= user_tags
            elif generator_match_cond is MatchCondition.SUPERSET:
                ok = gt >= user_tags
            else:
                ok = bool(gt & user_tags)
            if ok:
                out.extend(g.variants(constraints))
        return out


# ---------------------------------------------------------------------------
# Feature-value gathering (paper fig. 3, step 3)
# ---------------------------------------------------------------------------


def default_timer(kernel: MeasurementKernel, trials: int) -> TimingStats:
    """The default injectable timer: one real timing pass on the kernel,
    reported with its wall-clock noise."""
    return kernel.time_stats(trials=trials)


class CountingTimer:
    """Injectable timer wrapper that counts how many timing passes actually
    ran — the observable the measurement cache's zero-timing warm-path
    guarantee is asserted against (tests, CI smoke, CLI summary)."""

    def __init__(self, timer: Callable[[MeasurementKernel, int], TimerResult]
                 = default_timer):
        self._timer = timer
        self.calls = 0

    def __call__(self, kernel: MeasurementKernel, trials: int) -> TimerResult:
        self.calls += 1
        return self._timer(kernel, trials)


def _rel_std(stats: TimingStats) -> float:
    """Relative wall-clock spread of one measurement; inf when unknown
    (a spread-less measurement can never WIN a retime comparison, and a
    measurement without std is never retime-ELIGIBLE — gated separately,
    so bare-seconds timers don't read as infinitely noisy)."""
    if stats.std is None or not stats.median > 0:
        return float("inf")
    return stats.std / stats.median


def gather_feature_table(
    features: Sequence[str],
    kernels: Sequence[MeasurementKernel],
    *,
    trials: int = 20,
    timer: Optional[Callable[[MeasurementKernel, int], float]] = None,
    cache: Optional[Any] = None,
    retime_rel_std: Optional[float] = None,
    engine: Optional[Any] = None,
) -> FeatureTable:
    """Dense timing table: one row per measurement kernel, one column per
    feature id — the native input of the batched calibration pipeline.

    ``f_wall_time_*`` output features are *measured* (black box); all other
    features come from the automatic jaxpr counter.  One pass per kernel:
    each kernel is timed at most ONCE per gather regardless of how many
    wall-time columns the table has, and its jaxpr is counted once.

    ``timer(kernel, trials)`` makes the measurement injectable
    (deterministic tests, counters); it may return bare seconds or a
    :class:`TimingStats` (median/std/min — the noise metadata lands in
    ``FeatureTable.row_noise`` and the cache entry).  ``cache`` is a
    :class:`repro.profiles.MeasurementCache`-shaped object — on a cache hit
    neither the timer nor the jaxpr counter runs, so a warm recalibration
    performs zero timings.

    ``engine`` is a :class:`repro.core.countengine.CountEngine`-shaped
    object; with one, counts for cache-missing rows come from the engine —
    kernels carrying a symbolic family share one reconstruction and the
    whole size sweep's count matrix is filled by vectorized polynomial
    evaluation instead of one trace per size point.

    ``retime_rel_std`` is the noisy-row re-measurement heuristic (ROADMAP
    follow-up): rows whose relative wall-clock std exceeds the threshold
    get ONE extra timing pass before the table is returned — including
    rows served from the cache, since re-measuring noisy entries is the
    point — and the lower-spread measurement wins (and replaces the cache
    entry).  Re-timed row names are recorded in the returned table's
    ``retimed_rows`` so callers (CLI, ``PerfSession``) can surface how
    much of the battery was unstable.  Note this intentionally trades the
    warm-cache zero-timing guarantee for timing quality on noisy rows.

    Spans (:mod:`repro.spans`): ``measure.gather`` around the whole;
    inside it ``measure.cache`` around the lookups (``hits``,
    ``misses``) and each put, ``count.batch`` around the counting
    (``rows``, and with an engine ``traces`` and ``families_built``),
    and one ``measure.kernel`` per row.
    """
    with spans.span("measure.gather"):
        features = list(features)
        timer = timer or default_timer
        wall_cols = [j for j, f in enumerate(features)
                     if f.startswith("f_wall_time")]
        count_cols = [(j, f) for j, f in enumerate(features)
                      if not f.startswith("f_wall_time")]
        values = np.zeros((len(kernels), len(features)), np.float64)
        row_noise: Dict[str, Dict[str, float]] = {}
        retimed: List[str] = []
        entries: List[Any] = [None] * len(kernels)
        if cache is not None:
            with spans.span("measure.cache") as s:
                entries = [cache.get(k, trials) for k in kernels]
                s.attrs["hits"] = sum(e is not None for e in entries)
                s.attrs["misses"] = len(entries) - s.attrs["hits"]

        def put(k, wall, counts, stats) -> None:
            with spans.span("measure.cache", puts=1):
                cache.put(k, trials, wall, counts, noise=stats)

        # counts for every cache-missing row, resolved up front: the engine
        # batches symbolic families across the whole battery (vectorized
        # polynomial evaluation), so this is one pass, not one per row
        need = [i for i, e in enumerate(entries) if e is None]
        fresh_counts: Dict[int, FeatureCounts] = {}
        if need:
            with spans.span("count.batch", rows=len(need)) as s:
                if engine is not None:
                    before = engine.stats()
                    fresh_counts = dict(zip(
                        need, engine.counts_batch([kernels[i] for i in need])))
                    after = engine.stats()
                    s.attrs["traces"] = (after["trace_count"]
                                         - before["trace_count"])
                    s.attrs["families_built"] = (after["families"]
                                                 - before["families"])
                else:
                    fresh_counts = {i: kernels[i].counts() for i in need}
        # duplicate kernels in ONE cold gather (same name/sizes/code
        # identity) must be measured once — the pre-resolved entries above
        # can't see the put an earlier iteration performed, so track
        # in-gather results here
        local: Dict[Tuple, Tuple] = {}
        for i, k in enumerate(kernels):
            with spans.span("measure.kernel", kernel=k.name):
                entry = entries[i]
                kid = (k.name, tuple(sorted(k.sizes.items())), k.code_sig)
                if entry is None and kid in local:
                    counts, wall, stats = local[kid]
                    for j, f in count_cols:
                        values[i, j] = counts[f]
                    for j in wall_cols:
                        values[i, j] = wall
                    if stats is not None and (stats.std is not None
                                              or stats.min is not None):
                        row_noise[k.name] = stats.to_dict()
                    continue
                stats: Optional[TimingStats] = None
                if entry is not None:
                    counts, wall = entry.counts, entry.wall_time
                    stats = entry.noise
                    if wall_cols and wall is None:
                        # entry was gathered counts-only; backfill the timing
                        stats = TimingStats.coerce(timer(k, trials))
                        wall = stats.median
                        put(k, wall, counts, stats)
                else:
                    counts = fresh_counts[i]
                    if wall_cols:
                        stats = TimingStats.coerce(timer(k, trials))
                        wall = stats.median
                    else:
                        wall = None
                    if cache is not None:
                        put(k, wall, counts, stats)
                if (retime_rel_std is not None and wall_cols
                        and stats is not None and stats.std is not None
                        and _rel_std(stats) > retime_rel_std):
                    # noisy row: one extra pass; the steadier measurement wins
                    fresh = TimingStats.coerce(timer(k, trials))
                    retimed.append(k.name)
                    if _rel_std(fresh) < _rel_std(stats):
                        stats, wall = fresh, fresh.median
                        if cache is not None:
                            put(k, wall, counts, stats)
                if stats is not None and (stats.std is not None
                                          or stats.min is not None):
                    row_noise[k.name] = stats.to_dict()
                if entries[i] is None:
                    local[kid] = (counts, wall, stats)
                for j, f in count_cols:
                    values[i, j] = counts[f]
                for j in wall_cols:
                    values[i, j] = wall
        table = FeatureTable(features, values, [k.name for k in kernels],
                             row_noise)
        table.retimed_rows = retimed
        return table


def gather_feature_values(
    features: Sequence[str],
    kernels: Sequence[MeasurementKernel],
    *,
    trials: int = 20,
    timer: Optional[Callable[[MeasurementKernel, int], float]] = None,
    cache: Optional[Any] = None,
) -> List[Dict[str, float]]:
    """Deprecated dict-per-row view of :func:`gather_feature_table`."""
    warn_once(
        "gather_feature_values",
        "gather_feature_values is deprecated; use "
        "gather_feature_table(...).rows() (or the FeatureTable directly)")
    return gather_feature_table(features, kernels, trials=trials,
                                timer=timer, cache=cache).rows()


def unit_hash(*parts: object) -> float:
    """Deterministic draw in [0, 1) from the ':'-joined identity parts —
    THE unit-hash of the calibration subsystem (holdout assignment,
    synthetic-device noise).  One definition, so 'same identity → same
    draw, everywhere, forever' cannot silently diverge."""
    digest = hashlib.sha256(":".join(str(p) for p in parts).encode())
    return int(digest.hexdigest()[:12], 16) / float(16 ** 12)


def holdout_split(table: FeatureTable, *, holdout_fraction: float = 0.25,
                  salt: str = "holdout") -> Tuple[FeatureTable, FeatureTable]:
    """Deterministic train/held-out split of a gathered feature table.

    Assignment ranks rows by a hash of each *row name* (the
    measurement-kernel identity), not its position, and holds out the
    ``round(holdout_fraction · n)`` lowest-ranked rows (clamped so both
    sides are non-empty) — so the same kernel variant lands on the same
    side of the split on every machine, which is what makes per-variant
    held-out error columns comparable across profiles in a cross-machine
    study (paper §8's table shape), and the holdout size is exact rather
    than at the mercy of the hash draw.  ``salt`` derives independent
    splits from one battery.
    """
    if len(table) < 2:
        raise ValueError(
            f"cannot split a {len(table)}-row table into train + holdout")
    scores = {name: (unit_hash(salt, name), name)
              for name in table.row_names}
    order = sorted(range(len(table)), key=lambda i: scores[table.row_names[i]])
    k = int(round(holdout_fraction * len(table)))
    k = min(max(k, 1), len(table) - 1)
    hold = sorted(order[:k])
    train = sorted(order[k:])
    return table.select(train), table.select(hold)


# ---------------------------------------------------------------------------
# Built-in generators
# ---------------------------------------------------------------------------


def _dtype(s: str):
    return jnp.dtype({"float32": jnp.float32, "bfloat16": jnp.bfloat16,
                      "float64": jnp.float64}[s])


def _key(i=0):
    return jax.random.PRNGKey(i)


# ---- matmul_sq: the paper's running example --------------------------------


def _build_matmul_sq(*, n: int, dtype: str, prefetch: bool,
                     tile: int) -> MeasurementKernel:
    dt = _dtype(dtype)
    if prefetch:
        # blocked matmul: k-loop over [tile]-wide panels (the JAX analogue of
        # the local-memory prefetch variant — staged tiles, MXU-friendly)
        if n % tile:
            raise _SkipVariant
        nk = n // tile

        def fn(a, b):
            ar = a.reshape(n, nk, tile)

            def body(acc, i):
                ak = jax.lax.dynamic_slice_in_dim(ar, i, 1, axis=1)[:, 0]
                bk = jax.lax.dynamic_slice_in_dim(b, i * tile, tile, axis=0)
                return acc + ak @ bk, None

            acc, _ = jax.lax.scan(body, jnp.zeros((n, n), dt),
                                  jnp.arange(nk))
            return acc
    else:
        def fn(a, b):
            return a @ b

    def make_args():
        a = jax.random.normal(_key(1), (n, n), jnp.float32).astype(dt)
        b = jax.random.normal(_key(2), (n, n), jnp.float32).astype(dt)
        return a, b

    return MeasurementKernel(
        name=f"matmul_sq_n{n}_{dtype}_pf{prefetch}_t{tile}",
        fn=fn, make_args=make_args,
        tags=dict(n=n, dtype=dtype, prefetch=prefetch, tile=tile),
        sizes=dict(n=n))


MATMUL_SQ = Generator(
    "matmul_sq",
    frozenset({"matmul_sq", "matmul"}),
    arg_space=dict(
        n=(256, 384, 512, 640, 768, 1024),
        dtype=("float32", "bfloat16"),
        prefetch=(True, False),
        tile=(16, 32, 64, 128),
    ),
    build=_build_matmul_sq,
    # n³ madds (+ n² traffic); blocked variants need tile-aligned probes
    family=FamilySpec(
        var_degrees={"n": 3},
        probe=lambda **fx: (fx["tile"], fx["tile"]) if fx["prefetch"]
        else (16, 16),
    ),
)


# ---- flops_madd_pattern: peak-FLOP microbenchmark ---------------------------


def _build_madd(*, nelements: int, iters: int, dtype: str) -> MeasurementKernel:
    dt = _dtype(dtype)

    def fn(x, a, b):
        # 8 independent accumulator streams, 8-way unrolled madd chain —
        # the SHOC MaxFlops pattern (paper §7.1.2) vectorized per element
        xs = [x + jnp.asarray(i, dt) for i in range(8)]

        def body(i, xs):
            return [xi * a + b for xi in xs]

        xs = jax.lax.fori_loop(0, iters, body, xs)
        out = xs[0]
        for xi in xs[1:]:
            out = out + xi
        return out

    def make_args():
        x = jax.random.normal(_key(1), (nelements,), jnp.float32).astype(dt)
        return x, jnp.asarray(1.000001, dt), jnp.asarray(1e-7, dt)

    return MeasurementKernel(
        name=f"madd_n{nelements}_i{iters}_{dtype}",
        fn=fn, make_args=make_args,
        tags=dict(nelements=nelements, iters=iters, dtype=dtype),
        sizes=dict(nelements=nelements, iters=iters))


FLOPS_MADD = Generator(
    "flops_madd_pattern",
    frozenset({"flops_madd_pattern", "flops"}),
    arg_space=dict(
        nelements=(4096, 16384, 65536),
        iters=(64, 128, 256, 512),
        dtype=("float32", "bfloat16"),
    ),
    build=_build_madd,
    # per-element work × unrolled-loop trips: bilinear in (nelements, iters)
    family=FamilySpec(var_degrees={"nelements": 1, "iters": 1}),
)


# ---- flops_dot_pattern: contraction (MXU-class) madd throughput -------------
#
# TPU (and CPU BLAS) execute *contraction* madds on a different unit than
# elementwise FMAs — the MXU vs VPU dichotomy — so ``f_op_*_madd`` (dots)
# needs its own measurement kernel, distinct from the elementwise madd
# pattern above.  A cache/VMEM-resident square-matrix power chain reveals
# the peak contraction rate.


def _build_dot(*, n_dot: int, iters: int, dtype: str) -> MeasurementKernel:
    dt = _dtype(dtype)

    def fn(z, w):
        def body(c, _):
            c = c @ w
            # renormalize cheaply to avoid overflow across iterations
            return c * jnp.asarray(0.999, dt), None

        c, _ = jax.lax.scan(body, z, None, length=iters)
        return c

    def make_args():
        z = jax.random.normal(_key(1), (n_dot, n_dot), jnp.float32)
        w = jax.random.normal(_key(2), (n_dot, n_dot), jnp.float32)
        w = w / jnp.linalg.norm(w, axis=0, keepdims=True)
        return z.astype(dt), w.astype(dt)

    return MeasurementKernel(
        name=f"dotflops_n{n_dot}_i{iters}_{dtype}",
        fn=fn, make_args=make_args,
        tags=dict(n_dot=n_dot, iters=iters, dtype=dtype),
        sizes=dict(n_dot=n_dot, iters=iters))


FLOPS_DOT = Generator(
    "flops_dot_pattern",
    frozenset({"flops_dot_pattern", "flops"}),
    arg_space=dict(
        n_dot=(128, 256, 384),
        iters=(16, 64, 128),
        dtype=("float32", "bfloat16"),
    ),
    build=_build_dot,
    # n³ madds per chain step × iters steps
    family=FamilySpec(var_degrees={"n_dot": 3, "iters": 1}),
)


# ---- mem_stream: global-memory access patterns ------------------------------


def _build_stream(*, nelements: int, pattern: str, n_arrays: int,
                  dtype: str) -> MeasurementKernel:
    dt = _dtype(dtype)
    side = int(np.sqrt(nelements))

    if pattern == "contig":
        def fn(*arrs):
            out = arrs[0]
            for a in arrs[1:]:
                out = out + a
            return out

        def make_args():
            return tuple(
                jax.random.normal(_key(i), (nelements,), jnp.float32).astype(dt)
                for i in range(n_arrays))
    elif pattern == "strided":
        def fn(*arrs):
            out = arrs[0].T
            for a in arrs[1:]:
                out = out + a.T  # transposed read — lane-unfriendly layout
            return out

        def make_args():
            return tuple(
                jax.random.normal(_key(i), (side, side), jnp.float32).astype(dt)
                for i in range(n_arrays))
    elif pattern == "gather":
        def fn(idx, *arrs):
            out = arrs[0][idx]
            for a in arrs[1:]:
                out = out + a[idx]
            return out

        def make_args():
            idx = jax.random.randint(_key(9), (nelements,), 0, nelements)
            return (idx,) + tuple(
                jax.random.normal(_key(i), (nelements,), jnp.float32).astype(dt)
                for i in range(n_arrays))
    elif pattern == "shift":
        # rolled/concatenated access — the lowering jnp.roll produces;
        # distinct cost class on hosts where concat materializes copies
        def fn(*arrs):
            out = jnp.roll(arrs[0], 1)
            for a in arrs[1:]:
                out = out + jnp.roll(a, 1)
            return out

        def make_args():
            return tuple(
                jax.random.normal(_key(i), (nelements,), jnp.float32).astype(dt)
                for i in range(n_arrays))
    else:
        raise _SkipVariant

    return MeasurementKernel(
        name=f"stream_{pattern}_n{nelements}_a{n_arrays}_{dtype}",
        fn=fn, make_args=make_args,
        tags=dict(nelements=nelements, pattern=pattern, n_arrays=n_arrays,
                  dtype=dtype),
        sizes=dict(nelements=nelements))


MEM_STREAM = Generator(
    "mem_stream",
    frozenset({"mem_stream", "gmem"}),
    arg_space=dict(
        nelements=(262144, 1048576, 4194304, 16777216),
        pattern=("contig", "strided", "gather", "shift"),
        n_arrays=(1, 2, 4),
        dtype=("float32", "bfloat16"),
    ),
    build=_build_stream,
    # element traffic is linear in nelements — EXCEPT the strided pattern,
    # whose working shape is (isqrt(n), isqrt(n)): isqrt(n)² is not a
    # polynomial in n, so that pattern keeps exact per-shape tracing
    family=FamilySpec(
        var_degrees={"nelements": 1},
        applies=lambda **fx: fx["pattern"] != "strided",
    ),
)


# ---- onchip_pattern: VMEM/cache-resident working set ------------------------


def _build_onchip(*, working_set: int, iters: int, dtype: str
                  ) -> MeasurementKernel:
    dt = _dtype(dtype)

    def fn(x):
        def body(i, x):
            return jnp.roll(x, 1) + x  # stays in cache/VMEM, load+store heavy

        return jax.lax.fori_loop(0, iters, body, x)

    def make_args():
        return (jax.random.normal(_key(1), (working_set,),
                                  jnp.float32).astype(dt),)

    return MeasurementKernel(
        name=f"onchip_w{working_set}_i{iters}_{dtype}",
        fn=fn, make_args=make_args,
        tags=dict(working_set=working_set, iters=iters, dtype=dtype),
        sizes=dict(working_set=working_set, iters=iters))


ONCHIP = Generator(
    "onchip_pattern",
    frozenset({"onchip_pattern", "lmem"}),
    arg_space=dict(
        working_set=(2048, 8192, 32768),
        iters=(64, 256, 1024),
        dtype=("float32",),
    ),
    build=_build_onchip,
    # load+store rounds over a resident buffer: bilinear
    family=FamilySpec(var_degrees={"working_set": 1, "iters": 1}),
)


# ---- empty / launch-overhead kernel ----------------------------------------


def _build_empty(*, nelements: int) -> MeasurementKernel:
    def fn(x):
        return x

    def make_args():
        return (jnp.zeros((nelements,), jnp.float32),)

    return MeasurementKernel(
        name=f"empty_n{nelements}", fn=fn, make_args=make_args,
        tags=dict(nelements=nelements), sizes=dict(nelements=nelements))


EMPTY = Generator(
    "empty_kernel",
    frozenset({"empty_kernel", "launch"}),
    arg_space=dict(nelements=(16, 1024, 65536)),
    build=_build_empty,
    # identity kernel: counts are size-independent (launch overhead only)
    family=FamilySpec(var_degrees={"nelements": 0}),
)


# ---- sync / loop-step overhead ----------------------------------------------


def _build_loopstep(*, steps: int) -> MeasurementKernel:
    def fn(x):
        def body(c, _):
            return c + 1.0, None

        c, _ = jax.lax.scan(body, x, None, length=steps)
        return c

    def make_args():
        return (jnp.zeros((), jnp.float32),)

    return MeasurementKernel(
        name=f"loopstep_s{steps}", fn=fn, make_args=make_args,
        tags=dict(steps=steps), sizes=dict(steps=steps))


LOOPSTEP = Generator(
    "sync_loop_pattern",
    frozenset({"sync_loop_pattern", "sync"}),
    arg_space=dict(steps=(64, 512, 4096, 32768)),
    build=_build_loopstep,
    family=FamilySpec(var_degrees={"steps": 1}),
)


# ---- overlap kernel (paper §7.4): 1 global read + m on-chip updates ---------


def _build_overlap(*, nelements: int, m: int, dtype: str) -> MeasurementKernel:
    dt = _dtype(dtype)

    def fn(x):
        # one pass over the large array (memory-bound part)
        s = jnp.sum(x, dtype=jnp.float32)
        # m on-chip update rounds over a small resident buffer
        buf = jnp.full((1024,), s.astype(dt))

        def body(i, b):
            return b * jnp.asarray(0.999, dt) + jnp.asarray(1e-5, dt)

        buf = jax.lax.fori_loop(0, m, body, buf)
        return jnp.sum(buf)

    def make_args():
        return (jax.random.normal(_key(1), (nelements,),
                                  jnp.float32).astype(dt),)

    return MeasurementKernel(
        name=f"overlap_n{nelements}_m{m}_{dtype}",
        fn=fn, make_args=make_args,
        tags=dict(nelements=nelements, m=m, dtype=dtype),
        sizes=dict(nelements=nelements, m=m))


OVERLAP = Generator(
    "overlap_pattern",
    frozenset({"overlap_pattern", "overlap"}),
    arg_space=dict(
        nelements=(4194304, 16777216),
        m=(0, 4, 16, 64, 256, 1024, 4096, 16384, 65536),
        dtype=("float32",),
    ),
    build=_build_overlap,
    # one linear pass over nelements + m fixed-size on-chip rounds
    family=FamilySpec(var_degrees={"nelements": 1, "m": 1}),
)


# ---- DG differentiation (paper §8.4) ----------------------------------------


def _build_dg(*, nelements_dg: int, nunit_nodes: int, nmatrices: int,
              variant: str, dtype: str) -> MeasurementKernel:
    dt = _dtype(dtype)
    K, N, M = nelements_dg, nunit_nodes, nmatrices

    if variant == "basic":
        def fn(dmat, u):
            return jnp.einsum("mij,kj->mki", dmat, u)
    elif variant == "u_pf":
        # contraction reassociated to reuse u across matrices ("prefetch u")
        def fn(dmat, u):
            d2 = dmat.reshape(M * N, N)
            r = jnp.einsum("pj,kj->pk", d2, u)
            return r.reshape(M, N, K).transpose(0, 2, 1)
    elif variant == "dmat_pf":
        # loop over matrices, each a plain GEMM ("prefetch diff_mat")
        def fn(dmat, u):
            def body(_, dm):
                return None, u @ dm.T

            _, r = jax.lax.scan(body, None, dmat)
            return r
    elif variant == "dmat_pf_T":
        # + transposed element-data layout (the paper's fastest variant)
        def fn(dmat, ut):
            def body(_, dm):
                return None, dm @ ut

            _, r = jax.lax.scan(body, None, dmat)
            return r
    else:
        raise _SkipVariant

    def make_args():
        dmat = jax.random.normal(_key(1), (M, N, N), jnp.float32).astype(dt)
        if variant == "dmat_pf_T":
            u = jax.random.normal(_key(2), (N, K), jnp.float32).astype(dt)
        else:
            u = jax.random.normal(_key(2), (K, N), jnp.float32).astype(dt)
        return dmat, u

    return MeasurementKernel(
        name=f"dg_{variant}_k{K}_n{N}_m{M}_{dtype}",
        fn=fn, make_args=make_args,
        tags=dict(nelements_dg=K, nunit_nodes=N, nmatrices=M,
                  variant=variant, dtype=dtype),
        sizes=dict(nelements_dg=K))


DG_DIFF = Generator(
    "dg_diff",
    frozenset({"dg_diff", "dg"}),
    arg_space=dict(
        nelements_dg=(8192, 16384, 32768, 65536),
        nunit_nodes=(64,),
        nmatrices=(3,),
        variant=("basic", "u_pf", "dmat_pf", "dmat_pf_T"),
        dtype=("float32",),
    ),
    build=_build_dg,
    # every variant is one contraction sweep, linear in element count
    family=FamilySpec(var_degrees={"nelements_dg": 1}),
)


# ---- 2-D five-point stencil (paper §8.5) ------------------------------------


def _build_stencil(*, n_grid: int, variant: str, dtype: str
                   ) -> MeasurementKernel:
    dt = _dtype(dtype)

    if variant == "roll":
        def fn(u):
            return (jnp.roll(u, 1, 0) + jnp.roll(u, -1, 0)
                    + jnp.roll(u, 1, 1) + jnp.roll(u, -1, 1) - 4.0 * u)
    elif variant == "slice":
        def fn(u):
            c = u[1:-1, 1:-1]
            return (u[:-2, 1:-1] + u[2:, 1:-1] + u[1:-1, :-2]
                    + u[1:-1, 2:] - 4.0 * c)
    else:
        raise _SkipVariant

    def make_args():
        return (jax.random.normal(_key(1), (n_grid, n_grid),
                                  jnp.float32).astype(dt),)

    return MeasurementKernel(
        name=f"stencil_{variant}_n{n_grid}_{dtype}",
        fn=fn, make_args=make_args,
        tags=dict(n_grid=n_grid, variant=variant, dtype=dtype),
        sizes=dict(n_grid=n_grid))


STENCIL = Generator(
    "finite_diff",
    frozenset({"finite_diff", "stencil"}),
    arg_space=dict(
        n_grid=(1024, 2048, 4096, 8192),
        variant=("roll", "slice"),
        dtype=("float32",),
    ),
    build=_build_stencil,
    family=FamilySpec(var_degrees={"n_grid": 2}),
)


ALL_GENERATORS: List[Generator] = [
    MATMUL_SQ, FLOPS_MADD, FLOPS_DOT, MEM_STREAM, ONCHIP, EMPTY, LOOPSTEP,
    OVERLAP, DG_DIFF, STENCIL,
]
