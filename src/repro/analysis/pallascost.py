"""Static cost analysis of ``pallas_call``: grid-scaled body counts plus
block-spec memory traffic — no kernel execution, no interpret-mode run.

A ``pallas_call`` equation carries everything a cost model needs, in its
*parameters*: the kernel-body jaxpr (per-grid-program work), the grid
(how many programs run), and one ``BlockMapping`` per operand (which HBM
block each program's window DMAs into VMEM).  This module turns that into
:class:`repro.core.counting.FeatureCounts`:

* **body counts** — the body jaxpr is walked with the ordinary counting
  vocabulary (``scan`` bodies multiplied by trip count) and scaled by the
  grid size.  Body-local memory features are renamed ``f_mem_*`` →
  ``f_vmem_*``: a ``slice`` of a VMEM-resident block is on-chip traffic,
  a different cost class from the HBM streams the calibration batteries
  measure.
* **exact grid-edge branches** — ``pl.when``/``cond`` whose predicate is
  a quasi-affine function of ``program_id`` (the ``k == 0`` init /
  ``k == n_k - 1`` flush idiom of every pipelined kernel) is resolved
  *per grid program*: each branch is charged exactly the fraction of
  programs that execute it (nested ``when``s condition on the enclosing
  branch's program set).  Only when the predicate is unresolvable — data
  dependent, or the grid exceeds the exact-enumeration limit — does the
  analyzer fall back to averaging across branches, and then it says so in
  :attr:`PallasCost.notes` (surfaced by :mod:`repro.analysis.scope` as
  the info-severity ``pallas-averaged-branch`` diagnostic).
* **HBM↔VMEM traffic** — for each blocked operand, the index map is
  evaluated (pure numpy, on abstract grid indices) over every grid point
  in lexicographic order; a block is (re)fetched exactly when its index
  tuple differs from the previous grid step's — the Pallas pipeline's
  revisit-elision semantics.  ``fetches × block elements`` lands in the
  battery-calibrated ``f_mem_contig_<dtype>_load``/``_store`` features
  (so the stock ``ovl_flop_mem`` rung prices it) and, in bytes, in the
  new ``f_mem_hbm_bytes_in``/``f_mem_hbm_bytes_out`` features.
* **ANY-space operands** (``pl.BlockSpec(memory_space=pl.ANY)``) have no
  real block pipeline; their traffic is whatever the body ``get``/``swap``
  touches — counted as HBM directly, which captures halo reads with
  AFR > 1 (e.g. the five-point stencil's ``(bm+2)×(bn+2)`` windows).

Index maps are interpreted, not executed: a tiny numpy evaluator covers
the quasi-affine vocabulary real maps use (±, ×-by-constant, truncating
``div``/``rem`` by constants — ``lax``'s C-style semantics, not numpy's
flooring ``//`` — comparisons, ``select_n``, nested ``jit``).  Anything
outside that vocabulary, a data-dependent grid, or scalar-prefetch
operands raises :class:`PallasUnanalyzable` with a precise reason
(``non-affine-index-map`` / ``dynamic-grid`` / ``scalar-prefetch``) that
:mod:`repro.analysis.scope` surfaces as the ``pallas-unanalyzable``
diagnostic.  The counting walker stays silent on unanalyzable calls —
the auditor, not the counter, is the reporting channel.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.core.counting import (
    CALL_PRIMITIVES,
    FeatureCounts,
    _count_jaxpr_into,
    _dt,
    _size,
    register_subjaxpr_handler,
)

#: grids beyond this many programs skip exact revisit-elision enumeration
#: and conservatively charge one fetch per grid step per operand
_ENUM_LIMIT = 1 << 22

#: feature ids carrying statically derived HBM↔VMEM traffic, in bytes
BYTES_IN_FEATURE = "f_mem_hbm_bytes_in"
BYTES_OUT_FEATURE = "f_mem_hbm_bytes_out"


class PallasUnanalyzable(Exception):
    """A ``pallas_call`` the static analyzer cannot cost, with a stable
    machine-readable ``reason``:

    * ``"dynamic-grid"`` — grid extents are runtime values;
    * ``"non-affine-index-map"`` — an index map uses vocabulary outside
      the quasi-affine set (e.g. products of grid indices);
    * ``"scalar-prefetch"`` — index maps consume scalar-prefetch
      operands, so block addressing is data dependent.
    """

    def __init__(self, reason: str, message: str):
        super().__init__(message)
        self.reason = reason
        self.message = message


@dataclass(frozen=True)
class OperandTraffic:
    """Statically derived HBM traffic of one blocked operand."""

    role: str               # "in" | "out"
    index: int              # operand position within its role
    dtype: str
    block_elems: int
    fetches: int            # grid steps on which the block (re)loads
    exact: bool             # False when the grid exceeded _ENUM_LIMIT

    @property
    def elems(self) -> int:
        return self.block_elems * self.fetches

    @property
    def bytes(self) -> int:
        return self.elems * np.dtype(self.dtype).itemsize


@dataclass(frozen=True)
class PallasCost:
    """One ``pallas_call``'s static cost: total feature counts (body ×
    grid + block traffic) plus the per-operand traffic table.  ``notes``
    records every analysis imprecision that did NOT make the call
    unanalyzable — today, ``cond`` branches whose predicate could not be
    resolved per grid program and were averaged instead."""

    grid: Tuple[int, ...]
    num_programs: int
    counts: FeatureCounts
    traffic: Tuple[OperandTraffic, ...]
    notes: Tuple[str, ...] = ()


# ---------------------------------------------------------------------------
# quasi-affine index-map interpretation (pure numpy, no jax execution)
# ---------------------------------------------------------------------------


class _NonAffine(Exception):
    def __init__(self, detail: str):
        super().__init__(detail)
        self.detail = detail


def _trunc_div(a, b):
    # lax.div on ints truncates toward zero; numpy's // floors
    q = np.floor_divide(np.abs(a), np.abs(b))
    return q * np.sign(a) * np.sign(b)


def _np_dtype(dt) -> np.dtype:
    return np.dtype(str(dt))


class _Val:
    """An interpreted value: a numpy array (broadcast over grid points)
    plus whether it depends on the grid indices — the dependence flag is
    what turns ``mul`` of two grid values or ``div`` by a grid value into
    a structural non-affinity."""

    __slots__ = ("arr", "dep")

    def __init__(self, arr, dep: bool):
        self.arr = arr
        self.dep = dep


def _read(env: Dict[Any, _Val], v) -> _Val:
    if hasattr(v, "val"):           # jax literal
        return _Val(np.asarray(v.val), False)
    return env[v]


def _maybe_val(env: Dict[Any, _Val], v) -> Optional[_Val]:
    """Like :func:`_read` but ``None`` for a variable the interpreter has
    not resolved — the body-walk scalar tracker's partial-knowledge
    read (an index map, by contrast, must resolve everything)."""
    if hasattr(v, "val"):
        return _Val(np.asarray(v.val), False)
    return env.get(v)


def _binop(fn, a: _Val, b: _Val) -> _Val:
    return _Val(fn(a.arr, b.arr), a.dep or b.dep)


def _interp_eqn(eqn, env: Dict[Any, _Val]) -> None:
    prim = eqn.primitive.name
    ins = [_read(env, v) for v in eqn.invars]

    def out(val: _Val) -> None:
        env[eqn.outvars[0]] = val

    if prim in ("add", "add_any"):
        return out(_binop(np.add, *ins))
    if prim == "sub":
        return out(_binop(np.subtract, *ins))
    if prim == "mul":
        if ins[0].dep and ins[1].dep:
            raise _NonAffine("product of two grid-dependent values")
        return out(_binop(np.multiply, *ins))
    if prim == "div":
        if ins[1].dep:
            raise _NonAffine("division by a grid-dependent value")
        if np.issubdtype(np.asarray(ins[0].arr).dtype, np.integer):
            return out(_Val(_trunc_div(ins[0].arr, ins[1].arr), ins[0].dep))
        return out(_binop(np.divide, *ins))
    if prim == "rem":
        if ins[1].dep:
            raise _NonAffine("remainder by a grid-dependent value")
        r = ins[0].arr - ins[1].arr * _trunc_div(ins[0].arr, ins[1].arr)
        return out(_Val(r, ins[0].dep))
    if prim == "max":
        return out(_binop(np.maximum, *ins))
    if prim == "min":
        return out(_binop(np.minimum, *ins))
    if prim == "neg":
        return out(_Val(np.negative(ins[0].arr), ins[0].dep))
    if prim == "abs":
        return out(_Val(np.abs(ins[0].arr), ins[0].dep))
    if prim == "sign":
        return out(_Val(np.sign(ins[0].arr), ins[0].dep))
    if prim == "clamp":
        return out(_Val(np.clip(ins[1].arr, ins[0].arr, ins[2].arr),
                        any(x.dep for x in ins)))
    if prim in ("eq", "ne", "lt", "le", "gt", "ge"):
        fn = {"eq": np.equal, "ne": np.not_equal, "lt": np.less,
              "le": np.less_equal, "gt": np.greater,
              "ge": np.greater_equal}[prim]
        return out(_binop(fn, *ins))
    if prim in ("and", "or", "xor", "not"):
        if prim == "not":
            return out(_Val(np.logical_not(ins[0].arr), ins[0].dep))
        fn = {"and": np.logical_and, "or": np.logical_or,
              "xor": np.logical_xor}[prim]
        a, b = ins[0].arr, ins[1].arr
        if not (np.asarray(a).dtype == np.bool_
                and np.asarray(b).dtype == np.bool_):
            fn = {"and": np.bitwise_and, "or": np.bitwise_or,
                  "xor": np.bitwise_xor}[prim]
        return out(_Val(fn(a, b), ins[0].dep or ins[1].dep))
    if prim == "select_n":
        pred, *cases = ins
        acc = cases[0].arr
        for i in range(1, len(cases)):
            acc = np.where(np.asarray(pred.arr) == i, cases[i].arr, acc)
        return out(_Val(acc, any(x.dep for x in ins)))
    if prim == "convert_element_type":
        dt = _np_dtype(eqn.params["new_dtype"])
        return out(_Val(np.asarray(ins[0].arr).astype(dt), ins[0].dep))
    if prim in ("broadcast_in_dim", "squeeze", "reshape", "copy",
                "stop_gradient", "reduce_precision"):
        if eqn.outvars[0].aval.shape != ():
            raise _NonAffine(f"non-scalar {prim!r} in an index map")
        return out(_Val(ins[0].arr, ins[0].dep))
    if prim in CALL_PRIMITIVES:
        sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        jx = sub.jaxpr if hasattr(sub, "jaxpr") else sub
        consts = list(getattr(sub, "consts", ()))
        sub_env: Dict[Any, _Val] = {}
        for var, c in zip(jx.constvars, consts):
            sub_env[var] = _Val(np.asarray(c), False)
        for var, val in zip(jx.invars, ins):
            sub_env[var] = val
        for sub_eqn in jx.eqns:
            _interp_eqn(sub_eqn, sub_env)
        for ov, iv in zip(eqn.outvars, jx.outvars):
            env[ov] = _read(sub_env, iv)
        return
    raise _NonAffine(f"primitive {prim!r} outside the quasi-affine "
                     f"index-map vocabulary")


def _interp_index_map(closed_jaxpr, grid_axes: List[np.ndarray]
                      ) -> np.ndarray:
    """Evaluate one index map over all grid points: returns an
    ``(n_points, n_outputs)`` int64 array.  Raises :class:`_NonAffine`
    for vocabulary outside the quasi-affine set."""
    jx = closed_jaxpr.jaxpr
    env: Dict[Any, _Val] = {}
    for var, c in zip(jx.constvars, closed_jaxpr.consts):
        env[var] = _Val(np.asarray(c), False)
    if len(jx.invars) != len(grid_axes):
        raise _NonAffine(
            f"index map takes {len(jx.invars)} operands for "
            f"{len(grid_axes)} grid axes")
    for var, axis in zip(jx.invars, grid_axes):
        env[var] = _Val(axis, True)
    for eqn in jx.eqns:
        _interp_eqn(eqn, env)
    n = grid_axes[0].shape[0] if grid_axes else 1
    cols = [np.broadcast_to(np.asarray(_read(env, ov).arr, np.int64), (n,))
            for ov in jx.outvars]
    return np.stack(cols, axis=1) if cols else np.zeros((n, 0), np.int64)


def _fetches(outs: np.ndarray) -> int:
    """Grid steps on which the block index tuple differs from the
    previous step's — the Pallas pipeline (re)fetches exactly then."""
    n = outs.shape[0]
    if n <= 1:
        return n
    return int(np.any(outs[1:] != outs[:-1], axis=1).sum()) + 1


# ---------------------------------------------------------------------------
# the analyzer
# ---------------------------------------------------------------------------


def _static_grid(eqn) -> Tuple[int, ...]:
    gm = eqn.params["grid_mapping"]
    if getattr(gm, "num_dynamic_grid_bounds", 0):
        raise PallasUnanalyzable(
            "dynamic-grid",
            "grid extents are runtime values (dynamic grid bounds): the "
            "program count is unknowable statically")
    grid = []
    for g in gm.grid:
        try:
            grid.append(int(g))
        except (TypeError, ValueError):
            raise PallasUnanalyzable(
                "dynamic-grid",
                f"grid extent {g!r} is not a static integer") from None
    return tuple(grid)


def _require_analyzable(eqn) -> Tuple[int, ...]:
    """The cheap gates: static grid, no scalar prefetch.  Returns the
    grid.  Index-map affinity is checked during interpretation."""
    gm = eqn.params["grid_mapping"]
    grid = _static_grid(eqn)
    if getattr(gm, "num_index_operands", 0):
        raise PallasUnanalyzable(
            "scalar-prefetch",
            f"{gm.num_index_operands} scalar-prefetch operand(s) feed the "
            f"index maps: block addressing is data dependent")
    return grid


def _grid_axes(grid: Tuple[int, ...]) -> Tuple[List[np.ndarray], bool]:
    """Lexicographic (last-axis-fastest) grid enumeration, one int64
    column per axis.  Grids beyond :data:`_ENUM_LIMIT` are probed on a
    clipped grid (≤ 3 per axis) — enough to exercise the index-map
    vocabulary — and flagged inexact."""
    n = int(np.prod(grid)) if grid else 1
    exact = n <= _ENUM_LIMIT
    probe = grid if exact else tuple(min(g, 3) for g in grid)
    idx = np.indices(probe, dtype=np.int64)
    axes = [a.reshape(-1) for a in idx] if grid else []
    return axes, exact


def _is_any_space(aval) -> bool:
    ms = getattr(aval, "memory_space", None)
    return getattr(ms, "value", None) == "any" if ms is not None else False


def _block_elems(block_shape) -> int:
    # entries are ``Blocked``/``Element`` dims (``block_size``) or
    # ``Squeezed`` (a unit dim with no size)
    n = 1
    for b in block_shape:
        size = getattr(b, "block_size", b)
        if isinstance(size, (int, np.integer)):
            n *= int(size)
    return n


def _vmemify(feature: str) -> str:
    """Body-local memory features become VMEM-class: a slice of a
    VMEM-resident block is on-chip traffic, not an HBM stream."""
    if feature.startswith("f_mem_"):
        return "f_vmem_" + feature[len("f_mem_"):]
    return feature


def analyze_pallas_call(eqn) -> PallasCost:
    """Statically cost one ``pallas_call`` equation.

    Raises :class:`PallasUnanalyzable` (with a stable ``reason``) when
    the call is outside the analyzable set; never executes anything.
    """
    grid = _require_analyzable(eqn)
    gm = eqn.params["grid_mapping"]
    body = eqn.params["jaxpr"]
    num_programs = int(np.prod(grid)) if grid else 1

    # body refs: [inputs..., outputs..., scratch...] (no prefetch here)
    n_in, n_out = gm.num_inputs, gm.num_outputs
    operand_refs = body.invars[:n_in + n_out]
    any_refs = {id(v) for v in operand_refs if _is_any_space(v.aval)}

    # grid enumeration is shared by the body walk (per-program branch
    # resolution) and the block-traffic pass below
    axes, exact = _grid_axes(grid)
    n_points = axes[0].shape[0] if axes else 1

    # ---- body walk: ANY-ref accesses become HBM traffic, cond branches
    # with program_id-derived predicates are charged per grid program, and
    # the rest is ordinary counting with memory features downgraded to
    # VMEM class
    hbm = FeatureCounts()
    notes: List[str] = []
    # scalar dataflow over the grid: var → value at every grid point.
    # program_id seeds it; ordinary scalar arithmetic extends it through
    # the same quasi-affine interpreter the index maps use.
    env: Dict[Any, _Val] = {}
    # the set of grid programs executing the current branch-nesting level:
    # a nested `when` conditions its branch fractions on the enclosing
    # branch's programs, so joint (not just marginal) weights are exact
    mask_stack: List[np.ndarray] = [np.ones(n_points, dtype=bool)]

    def _bind(jx, consts, outer_invars) -> None:
        """Carry known scalar values across a sub-jaxpr boundary."""
        for var, c in zip(jx.constvars, consts):
            if getattr(c, "shape", None) == ():
                env[var] = _Val(np.asarray(c), False)
        for var, outer in zip(jx.invars, outer_invars):
            val = _maybe_val(env, outer)
            if val is not None:
                env[var] = val

    def override(sub_eqn, counts_acc, mult) -> bool:
        prim = sub_eqn.primitive.name
        if prim in ("get", "swap", "addupdate"):
            if id(sub_eqn.invars[0]) not in any_refs \
                    and not _is_any_space(sub_eqn.invars[0].aval):
                return False
            ref_dt = _dt(sub_eqn.invars[0].aval)
            nbytes = np.dtype(ref_dt).itemsize
            if prim == "get":
                elems = _size(sub_eqn.outvars[0].aval)
                hbm.add(f"f_mem_contig_{ref_dt}_load", elems * mult)
                hbm.add(BYTES_IN_FEATURE, elems * nbytes * mult)
            elif prim == "swap":
                elems = _size(sub_eqn.outvars[0].aval)
                hbm.add(f"f_mem_contig_{ref_dt}_store", elems * mult)
                hbm.add(BYTES_OUT_FEATURE, elems * nbytes * mult)
            else:           # addupdate: read-modify-write
                elems = _size(sub_eqn.invars[1].aval)
                hbm.add(f"f_mem_contig_{ref_dt}_load", elems * mult)
                hbm.add(f"f_mem_contig_{ref_dt}_store", elems * mult)
                hbm.add(BYTES_IN_FEATURE, elems * nbytes * mult)
                hbm.add(BYTES_OUT_FEATURE, elems * nbytes * mult)
            return True
        if prim == "program_id":
            if exact and axes:
                env[sub_eqn.outvars[0]] = _Val(
                    axes[sub_eqn.params["axis"]], True)
            return False        # stays zero-cost; counted normally
        if prim == "num_programs":
            ax = sub_eqn.params["axis"]
            env[sub_eqn.outvars[0]] = _Val(
                np.asarray(grid[ax], np.int64), False)
            return False
        if prim == "cond":
            branches = sub_eqn.params["branches"]
            if not exact:
                notes.append(
                    f"grid {grid} exceeds the exact-enumeration limit "
                    f"({_ENUM_LIMIT} programs): cond branch costs are "
                    f"averaged across {len(branches)} branches")
                return False    # default averaging in _count_eqn
            idx_val = _maybe_val(env, sub_eqn.invars[0])
            if idx_val is None:
                notes.append(
                    f"cond predicate is not a resolvable function of "
                    f"program_id: branch costs are averaged across "
                    f"{len(branches)} branches")
                return False
            sel = np.broadcast_to(
                np.clip(np.asarray(idx_val.arr).astype(np.int64),
                        0, len(branches) - 1), (n_points,))
            mask = mask_stack[-1]
            live = int(mask.sum())
            for b, br in enumerate(branches):
                jx = br.jaxpr
                _bind(jx, br.consts, sub_eqn.invars[1:])
                bmask = mask & (sel == b)
                took = int(bmask.sum())
                if took == 0:
                    continue    # no program takes this branch: zero cost
                mask_stack.append(bmask)
                try:
                    _count_jaxpr_into(jx, counts_acc,
                                      mult * (took / live),
                                      override=override)
                finally:
                    mask_stack.pop()
            return True
        if prim in CALL_PRIMITIVES:
            sub = sub_eqn.params.get("jaxpr") \
                or sub_eqn.params.get("call_jaxpr")
            if sub is not None:
                jx = sub.jaxpr if hasattr(sub, "jaxpr") else sub
                _bind(jx, getattr(sub, "consts", ()), sub_eqn.invars)
            return False        # normal counting recurses with override
        # ordinary scalar equation: extend the dataflow when every operand
        # is known (best effort — unresolved vars just stop the chain)
        if len(sub_eqn.outvars) == 1 \
                and getattr(sub_eqn.outvars[0].aval, "shape", None) == () \
                and all(_maybe_val(env, v) is not None
                        for v in sub_eqn.invars):
            try:
                _interp_eqn(sub_eqn, env)
            except _NonAffine:
                pass
        return False

    body_counts = FeatureCounts()
    _count_jaxpr_into(body, body_counts, 1.0, override=override)

    total = FeatureCounts()
    for k, v in body_counts.items():
        total.add(_vmemify(k), v * num_programs)
    for k, v in hbm.items():
        total.add(k, v * num_programs)

    # ---- block-spec HBM traffic: fetches = index-map runs over the grid
    traffic: List[OperandTraffic] = []
    mappings = list(gm.block_mappings)
    for pos, bm in enumerate(mappings):
        role = "in" if pos < n_in else "out"
        idx = pos if pos < n_in else pos - n_in
        ref = operand_refs[pos] if pos < len(operand_refs) else None
        if ref is not None and id(ref) in any_refs:
            continue        # no block pipeline; body get/swap counted it
        try:
            outs = _interp_index_map(bm.index_map_jaxpr, axes)
        except _NonAffine as e:
            raise PallasUnanalyzable(
                "non-affine-index-map",
                f"operand {pos} ({role}) index map is not quasi-affine "
                f"in the grid indices: {e.detail}") from None
        fetches = _fetches(outs) if exact else num_programs
        dt = str(bm.array_aval.dtype)
        t = OperandTraffic(role=role, index=idx, dtype=dt,
                           block_elems=_block_elems(bm.block_shape),
                           fetches=fetches, exact=exact)
        traffic.append(t)
        kind = "load" if role == "in" else "store"
        total.add(f"f_mem_contig_{dt}_{kind}", t.elems)
        total.add(BYTES_IN_FEATURE if role == "in" else BYTES_OUT_FEATURE,
                  t.bytes)

    total.add("f_sync_grid_programs", num_programs)
    return PallasCost(grid=grid, num_programs=num_programs,
                      counts=total, traffic=tuple(traffic),
                      notes=tuple(dict.fromkeys(notes)))


def unanalyzable_reason(eqn) -> Optional[PallasUnanalyzable]:
    """``None`` when the call is statically analyzable, else the typed
    :class:`PallasUnanalyzable` naming why — the scope auditor's probe
    (it runs the same gates + index-map interpretation, no body walk)."""
    try:
        grid = _require_analyzable(eqn)
        axes, _exact = _grid_axes(grid)
        gm = eqn.params["grid_mapping"]
        body = eqn.params["jaxpr"]
        n_ops = gm.num_inputs + gm.num_outputs
        operand_refs = body.invars[:n_ops]
        for pos, bm in enumerate(gm.block_mappings):
            if pos < len(operand_refs) \
                    and _is_any_space(operand_refs[pos].aval):
                continue
            try:
                _interp_index_map(bm.index_map_jaxpr, axes)
            except _NonAffine as e:
                role = "in" if pos < gm.num_inputs else "out"
                raise PallasUnanalyzable(
                    "non-affine-index-map",
                    f"operand {pos} ({role}) index map is not "
                    f"quasi-affine in the grid indices: {e.detail}"
                ) from None
    except PallasUnanalyzable as e:
        return e
    return None


def count_pallas_call(eqn, counts: FeatureCounts, mult: float) -> None:
    """Sub-jaxpr counting handler for ``pallas_call`` (registered with
    :func:`repro.core.counting.register_subjaxpr_handler`).  Unanalyzable
    calls contribute nothing — the scope auditor, not the counter, names
    why (``pallas-unanalyzable``)."""
    try:
        cost = analyze_pallas_call(eqn)
    except PallasUnanalyzable:
        return
    for k, v in cost.counts.items():
        counts.add(k, v * mult)


register_subjaxpr_handler("pallas_call", count_pallas_call)
