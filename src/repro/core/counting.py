"""Automatic kernel-statistics gathering from jaxprs (paper §5, Algorithm 1).

The paper walks a polyhedral program representation, counting per-statement
operations × statement trip counts.  The JAX analogue walks a
``ClosedJaxpr``: equations inside ``scan``/``while`` bodies are multiplied
by the (statically known) trip count, ``cond`` branches are averaged
(matching the paper's divergent-control-flow cost accounting — except
inside Pallas kernel bodies, where the static cost analyzer resolves
``program_id``-derived predicates and charges each grid program its
actual branch), and ``jit``/``remat`` calls (:data:`CALL_PRIMITIVES`) are
inlined.

Counted feature classes (the TPU translation of the paper's features):
  * arithmetic  — by (op-kind, dtype); ``dot_general`` is counted as *madd*
    sequences (the MXU's fused multiply-add), exactly the paper's
    ``f_op_<dtype>_madd``
  * memory      — element traffic by access class: ``contig`` (last-dim
    contiguous, lane-friendly), ``strided`` (transpose/reorder),
    ``gather``/``scatter`` (irregular).  On GPU the paper keys cost on
    lid-strides; on TPU the analogous cost driver is (sublane, lane)
    layout friendliness.
  * collective  — payload bytes by collective kind (psum, all_gather, ...)
  * sync        — program launches, loop steps, pallas grid programs

``pallas_call`` is opened, not skipped: a registered sub-jaxpr handler
(:mod:`repro.analysis.pallascost`, imported lazily on first encounter)
walks the kernel body per grid program, scales by the grid size, and adds
block-spec HBM↔VMEM traffic (``f_mem_hbm_bytes_in``/``_out`` plus the
battery-calibrated ``f_mem_contig_*`` element classes).  Other opaque
wrappers can register the same way via
:func:`register_subjaxpr_handler`.
"""
from __future__ import annotations

import importlib
import itertools
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax
import numpy as np

from repro.core.symbolic import ParametricCount, Poly, interpolate_polynomial


# ---------------------------------------------------------------------------
# Feature-count container
# ---------------------------------------------------------------------------


class FeatureCounts(dict):
    """Mapping feature-id → count (float).  Missing keys read as 0."""

    def __missing__(self, key):
        return 0.0

    def add(self, key: str, value: float):
        self[key] = self.get(key, 0.0) + float(value)

    def merged(self, other: "FeatureCounts", mult: float = 1.0
               ) -> "FeatureCounts":
        out = FeatureCounts(self)
        for k, v in other.items():
            out.add(k, v * mult)
        return out

    def scaled(self, mult: float) -> "FeatureCounts":
        return FeatureCounts({k: v * mult for k, v in self.items()})


def _size(aval) -> int:
    return int(np.prod(aval.shape)) if aval.shape else 1


def _dt(aval) -> str:
    return str(aval.dtype)


_ARITH = {
    "add": "add", "add_any": "add", "sub": "add", "mul": "mul",
    "div": "div", "max": "cmp", "min": "cmp", "neg": "add",
    "exp": "transc", "log": "transc", "tanh": "transc", "logistic": "transc",
    "rsqrt": "transc", "sqrt": "transc", "erf": "transc", "sin": "transc",
    "cos": "transc", "pow": "transc", "square": "mul",
    "exp2": "transc", "log1p": "transc", "expm1": "transc",
    "cumsum": "add", "cumlogsumexp": "transc", "cummax": "cmp",
    "abs": "add",
}

_MEM_GATHER = {"gather", "take", "dynamic_slice"}
_MEM_SCATTER = {"scatter", "scatter-add", "scatter_add", "dynamic_update_slice"}
# element permutations; ``roll`` is Pallas TPU's lane/sublane rotation
_MEM_STRIDED = {"transpose", "rev", "roll"}
# concatenate gets its own access class: on most hosts it materializes a
# copy (jnp.roll lowers to it), with a distinct cost from streaming adds
_MEM_CONCAT = {"concatenate"}
_MEM_CONTIG = {"broadcast_in_dim", "pad", "slice", "squeeze",
               "expand_dims", "copy", "convert_element_type", "reshape",
               "iota", "select_n"}

# stateful ref accesses (Pallas kernel bodies, run_state): element traffic
# against the ref's memory space — the pallas analyzer reclassifies these
# per ref (VMEM block vs ANY/HBM operand)
_MEM_REF = {"get", "swap", "addupdate"}

_COLLECTIVES = {"psum", "all_gather", "reduce_scatter", "all_to_all",
                "ppermute", "pmax", "pmin", "psum_invariant",
                "all_gather_invariant"}


def _coll_name(prim: str) -> str:
    return prim[:-len("_invariant")] if prim.endswith("_invariant") else prim

_REDUCE = {"reduce_sum": "add", "reduce_max": "cmp", "reduce_min": "cmp",
           "reduce_prod": "mul", "argmax": "cmp", "argmin": "cmp",
           "reduce_and": "add", "reduce_or": "add"}


# ---------------------------------------------------------------------------
# Count vocabulary (exported for the static scope auditor, repro.analysis)
# ---------------------------------------------------------------------------

#: call primitives: a sub-jaxpr (``jaxpr`` or ``call_jaxpr`` param) run
#: once, so its cost is its body's cost.  The one table every walker
#: (counting, work removal, the pallas analyzer) dispatches on — ``jax.jit``
#: binds ``jit`` and ``jax.checkpoint`` binds ``remat2``.
CALL_PRIMITIVES = ("jit", "closed_call", "core_call", "remat2",
                   "custom_jvp_call", "custom_vjp_call")

# control-flow primitives the walker RECURSES into (their cost is their
# body's cost, possibly times a trip count) — must list exactly the prims
# _count_eqn handles structurally, or the auditor would misclassify them
CONTROL_PRIMITIVES = frozenset(
    {"scan", "while", "cond", "shard_map", *CALL_PRIMITIVES})

# primitives the counter DELIBERATELY treats as free.  These never earn a
# feature: predicates/bit ops ride along with the selects and arithmetic
# they gate, rng plumbing builds example inputs rather than kernel work,
# and the metadata prims exist only at trace time.  Everything the walker
# skips that is NOT in this set is an unmodeled gap — the scope auditor's
# reason to exist.
ZERO_COST_PRIMITIVES = frozenset({
    # predicates and boolean/bit bookkeeping
    "lt", "le", "gt", "ge", "eq", "ne", "and", "or", "not", "xor",
    "shift_left", "shift_right_logical", "shift_right_arithmetic",
    "population_count", "clz", "sign", "is_finite",
    # rng plumbing (input fabrication, not kernel work)
    "random_seed", "random_bits", "random_fold_in", "random_wrap",
    "random_unwrap", "threefry2x32",
    # trace-time metadata
    "stop_gradient", "device_put", "create_token", "optimization_barrier",
    "reduce_precision", "sharding_constraint", "split",
    # grid-coordinate reads inside pallas kernel bodies
    "program_id", "num_programs",
})

# primitives with bespoke counting rules in _count_eqn (not table-driven)
_SPECIAL = frozenset({"dot_general", "integer_pow", "sort"})


# ---------------------------------------------------------------------------
# Registered sub-jaxpr handlers — opaque-by-name primitives opened up by
# analysis passes (pallas_call's static cost analyzer registers here)
# ---------------------------------------------------------------------------

#: prim name → handler(eqn, counts, mult); the handler owns the whole
#: equation (recursing into whatever sub-jaxprs its params carry)
_SUBJAXPR_HANDLERS: Dict[str, Callable[[Any, "FeatureCounts", float],
                                       None]] = {}

#: prim name → module whose import registers that prim's handler; popped
#: on first use so a failed/absent registration is attempted only once
_LAZY_HANDLER_MODULES: Dict[str, str] = {
    "pallas_call": "repro.analysis.pallascost",
}


def register_subjaxpr_handler(
        prim: str,
        handler: Callable[[Any, "FeatureCounts", float], None]) -> None:
    """Register a counting handler for a primitive that wraps a
    sub-computation the table-driven walker cannot enter (``pallas_call``
    and friends).  The handler is called as ``handler(eqn, counts, mult)``
    and must fold the equation's whole cost into ``counts``."""
    _SUBJAXPR_HANDLERS[prim] = handler


def _handler_for(prim: str) -> Optional[Callable]:
    handler = _SUBJAXPR_HANDLERS.get(prim)
    if handler is None and prim in _LAZY_HANDLER_MODULES:
        mod = _LAZY_HANDLER_MODULES.pop(prim)
        try:
            importlib.import_module(mod)    # registers on import
        except ImportError:
            return None
        handler = _SUBJAXPR_HANDLERS.get(prim)
    return handler


def primitive_cost_class(prim: str) -> Optional[str]:
    """Classify one primitive name against the counter's vocabulary:
    ``"arith"``/``"reduce"``/``"memory"``/``"collective"``/``"special"``
    (all counted), ``"control"`` (recursed into), ``"zero"`` (deliberately
    free), or ``None`` — the primitive does work the counter has no rule
    for (an unmodeled scope gap, the scope auditor's error class)."""
    if prim in _ARITH:
        return "arith"
    if prim in _REDUCE:
        return "reduce"
    if prim in _MEM_GATHER or prim in _MEM_SCATTER or prim in _MEM_STRIDED \
            or prim in _MEM_CONCAT or prim in _MEM_CONTIG \
            or prim in _MEM_REF:
        return "memory"
    if prim in _COLLECTIVES:
        return "collective"
    if prim in _SPECIAL:
        return "special"
    if prim in CONTROL_PRIMITIVES:
        return "control"
    if prim in ZERO_COST_PRIMITIVES:
        return "zero"
    if _handler_for(prim) is not None:
        return "control"        # a registered handler enters its body
    return None


def _count_eqn(eqn, counts: FeatureCounts, mult: float,
               override: Optional[Callable] = None):
    prim = eqn.primitive.name
    # an analysis pass walking a sub-jaxpr may claim individual equations
    # (e.g. ref accesses against ANY-space operands) before any table rule
    if override is not None and override(eqn, counts, mult):
        return
    handler = _handler_for(prim)
    if handler is not None:
        handler(eqn, counts, mult)
        return
    out_aval = eqn.outvars[0].aval if eqn.outvars else None

    if prim == "dot_general":
        dims = eqn.params["dimension_numbers"]
        (lc, _), _ = dims
        lhs = eqn.invars[0].aval
        contract = 1
        for d in lc:
            contract *= lhs.shape[d]
        n_madd = _size(out_aval) * contract
        counts.add(f"f_op_{_dt(out_aval)}_madd", n_madd * mult)
        # operand/result element traffic, contiguous class
        for v in eqn.invars:
            counts.add(f"f_mem_contig_{_dt(v.aval)}_load", _size(v.aval) * mult)
        counts.add(f"f_mem_contig_{_dt(out_aval)}_store",
                   _size(out_aval) * mult)
        return

    if prim == "integer_pow":
        # square-and-multiply: x**p costs floor(log2 p) squarings plus
        # popcount(p)−1 extra multiplies per element, not |p|−1 and not 1
        # — x**8 is 3 squarings, x**7 is 4 muls (x², x³, x⁶, x⁷), x**2 is
        # 1.  |p| ≤ 1 is a free copy; a negative exponent adds the
        # reciprocal's divide.
        y = int(eqn.params["y"])
        p = abs(y)
        if p >= 2:
            n_mul = (p.bit_length() - 1) + (bin(p).count("1") - 1)
            counts.add(f"f_op_{_dt(out_aval)}_mul",
                       _size(out_aval) * n_mul * mult)
        if y < 0:
            counts.add(f"f_op_{_dt(out_aval)}_div",
                       _size(out_aval) * mult)
        return

    if prim in _ARITH:
        kind = _ARITH[prim]
        counts.add(f"f_op_{_dt(out_aval)}_{kind}", _size(out_aval) * mult)
        return

    if prim in _REDUCE:
        kind = _REDUCE[prim]
        counts.add(f"f_op_{_dt(eqn.invars[0].aval)}_{kind}",
                   _size(eqn.invars[0].aval) * mult)
        return

    if prim in _MEM_GATHER:
        counts.add(f"f_mem_gather_{_dt(out_aval)}_load",
                   _size(out_aval) * mult)
        return
    if prim in _MEM_SCATTER:
        upd = eqn.invars[-1].aval
        counts.add(f"f_mem_scatter_{_dt(upd)}_store", _size(upd) * mult)
        return
    if prim in _MEM_STRIDED:
        counts.add(f"f_mem_strided_{_dt(out_aval)}_load",
                   _size(out_aval) * mult)
        counts.add(f"f_mem_strided_{_dt(out_aval)}_store",
                   _size(out_aval) * mult)
        return
    if prim in _MEM_CONCAT:
        counts.add(f"f_mem_concat_{_dt(out_aval)}_store",
                   _size(out_aval) * mult)
        return
    if prim in _MEM_CONTIG:
        counts.add(f"f_mem_contig_{_dt(out_aval)}_store",
                   _size(out_aval) * mult)
        return
    if prim in _MEM_REF:
        # ref element traffic; the pallas analyzer renames these per the
        # ref's memory space (VMEM block vs ANY/HBM operand)
        if prim == "get":
            counts.add(f"f_mem_ref_{_dt(out_aval)}_load",
                       _size(out_aval) * mult)
        elif prim == "swap":
            counts.add(f"f_mem_ref_{_dt(out_aval)}_store",
                       _size(out_aval) * mult)
        else:               # addupdate: read-modify-write + the adds
            upd = eqn.invars[1].aval
            counts.add(f"f_mem_ref_{_dt(upd)}_load", _size(upd) * mult)
            counts.add(f"f_mem_ref_{_dt(upd)}_store", _size(upd) * mult)
            counts.add(f"f_op_{_dt(upd)}_add", _size(upd) * mult)
        return

    if prim in _COLLECTIVES:
        nbytes = sum(_size(v.aval) * v.aval.dtype.itemsize
                     for v in eqn.invars)
        counts.add(f"f_coll_{_coll_name(prim)}_bytes", nbytes * mult)
        counts.add(f"f_coll_{_coll_name(prim)}_count", mult)
        return

    if prim in ("sort",):
        n = _size(eqn.invars[0].aval)
        counts.add(f"f_op_{_dt(eqn.invars[0].aval)}_cmp",
                   n * max(np.log2(max(n, 2)), 1) * mult)
        return

    # ---- control flow: recurse into the SAME accumulator ------------------
    # the caller's FeatureCounts and a folded-in multiplier are passed down
    # instead of building a fresh dict per nesting level and re-merging
    # key-by-key — nesting depth costs stack frames only, never dict churn
    if prim == "scan":
        length = eqn.params["length"]
        _count_jaxpr_into(eqn.params["jaxpr"].jaxpr, counts, length * mult,
                          override=override)
        counts.add("f_sync_loop_steps", length * mult)
        return
    if prim == "while":
        # unknown trip count: charge body AND predicate once per visit (the
        # predicate runs trips+1 times; single-visit accounting charges 1)
        _count_jaxpr_into(eqn.params["body_jaxpr"].jaxpr, counts, mult,
                          override=override)
        _count_jaxpr_into(eqn.params["cond_jaxpr"].jaxpr, counts, mult,
                          override=override)
        counts.add("f_sync_loop_steps", mult)
        return
    if prim == "cond":
        branches = eqn.params["branches"]
        for br in branches:  # average — divergent-branch accounting (§4)
            _count_jaxpr_into(br.jaxpr, counts, mult / len(branches),
                              override=override)
        return
    if prim in CALL_PRIMITIVES or prim == "shard_map":
        sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        if sub is not None:
            jx = sub.jaxpr if hasattr(sub, "jaxpr") else sub
            _count_jaxpr_into(jx, counts, mult, override=override)
        return
    # everything else: ignore (shape ops, rng, etc.)


def _count_jaxpr_into(jaxpr, counts: FeatureCounts, mult: float,
                      override: Optional[Callable] = None) -> None:
    for eqn in jaxpr.eqns:
        _count_eqn(eqn, counts, mult, override=override)


def count_jaxpr_counts(jaxpr) -> FeatureCounts:
    counts = FeatureCounts()
    _count_jaxpr_into(jaxpr, counts, 1.0)
    return counts


def count_fn(fn: Callable, *example_args, **example_kwargs) -> FeatureCounts:
    """Count features of ``fn`` at concrete input shapes (Algorithm 1)."""
    jaxpr = jax.make_jaxpr(fn)(*example_args, **example_kwargs)
    counts = count_jaxpr_counts(jaxpr.jaxpr)
    counts.add("f_sync_launch_kernel", 1.0)
    return counts


# ---------------------------------------------------------------------------
# Parametric (symbolic) counts — cached piecewise-polynomial reconstruction
# ---------------------------------------------------------------------------


@dataclass
class SymbolicCounts:
    """Feature-id → ParametricCount, reconstructed once, evaluated cheaply."""

    counts: Dict[str, ParametricCount]
    assumptions: Tuple[str, ...]

    def at(self, **sizes) -> FeatureCounts:
        out = FeatureCounts()
        for k, pc in self.counts.items():
            out[k] = pc(**sizes)
        return out

    def at_batch(self, **sizes) -> Dict[str, np.ndarray]:
        """Vectorized evaluation over arrays of size values: one float64
        array per feature (constant features broadcast to the sweep
        shape).  A whole battery's count matrix from flat numpy, no
        per-size Python loop — the count engine's serving hot path."""
        shape = np.broadcast_shapes(
            *(np.asarray(v).shape for v in sizes.values())) \
            if sizes else ()
        return {k: np.broadcast_to(pc.eval_batch(**sizes), shape)
                for k, pc in self.counts.items()}


def parametric_counts_from(
    probe: Callable[..., FeatureCounts],
    var_degrees: Mapping[str, int],
    *,
    base: int = 16,
    scale: int = 16,
) -> SymbolicCounts:
    """Reconstruct symbolic counts from an arbitrary per-size prober.

    ``probe(**sizes) -> FeatureCounts`` counts one concrete instantiation
    (it may build a *different* callable per size — kernel families whose
    bodies close over the size go through here); it is invoked exactly
    once per grid point.  Counts of static-control programs are polynomial
    in each size, so exact Lagrange interpolation over ``degree+1`` probe
    values per variable recovers the full symbolic form.
    """
    feature_ids = set()
    cache: Dict[Tuple, FeatureCounts] = {}

    def cached_probe(**sizes) -> FeatureCounts:
        key = tuple(sorted(sizes.items()))
        if key not in cache:
            cache[key] = probe(**sizes)
            feature_ids.update(cache[key].keys())
        return cache[key]

    # probe the FULL interpolation grid before enumerating features: a
    # feature may be absent at the base size yet appear at larger probes
    # (e.g. a scan that vanishes when n == tile), and freezing the feature
    # set after one probe would silently drop its polynomial
    names = sorted(var_degrees)
    grids = [[base + scale * i for i in range(var_degrees[v] + 1)]
             for v in names]
    for combo in itertools.product(*grids):
        cached_probe(**dict(zip(names, combo)))
    polys: Dict[str, ParametricCount] = {}
    assumptions = tuple(f"{v} % {scale} == 0" for v in var_degrees)
    for fid in sorted(feature_ids):
        p = interpolate_polynomial(
            lambda **sizes: cached_probe(**sizes)[fid], var_degrees,
            base=base, scale=scale)
        polys[fid] = ParametricCount(p, assumptions)
    return SymbolicCounts(polys, assumptions)


def parametric_counts(
    make_args: Callable[..., tuple],
    fn: Callable,
    var_degrees: Mapping[str, int],
    *,
    base: int = 16,
    scale: int = 16,
) -> SymbolicCounts:
    """Reconstruct symbolic counts parametric in named size variables.

    ``make_args(**sizes)`` builds (abstract) example arguments for ``fn`` at
    given sizes; counts are probed on a small grid and interpolated exactly
    (counts of static-control programs are polynomial in each size).
    The result re-evaluates in microseconds for any problem size —
    the paper's amortization property.
    """
    return parametric_counts_from(
        lambda **sizes: count_fn(fn, *make_args(**sizes)),
        var_degrees, base=base, scale=scale)
