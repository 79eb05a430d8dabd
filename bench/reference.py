"""The plain reference that decides ``correct``.  It imports nothing of the
program under test and takes nothing it made but its answers.

* **Counts.**  A straightforward walk of the request's jaxpr (``jax``
  only), for the five features the zoo's rungs price: dot madds, adds,
  contiguous loads and stores of float32, and one launch.  ``scan``
  bodies are charged ``length`` times, ``while`` bodies once, ``cond``
  branches on average, calls once.  A ``pallas_call`` is charged its body
  once per grid program (with memory inside the body on-chip, so not
  priced), its ``ANY``-space reads and writes as HBM traffic, and for each
  blocked operand one block per change of block index over the grid in
  order.  A ``cond`` inside a kernel body is charged per grid program
  where its index follows from ``program_id``.
* **Prices.**  Each rung's formula, written out here, in float64.
* **Fits.**  Each linear rung fitted in float64 by scipy (bounded linear
  least squares) on the same measured rows and the same held-out split,
  to compare the program's fit against.
"""
from __future__ import annotations

import hashlib
from typing import Any, Dict, List, Mapping, Sequence, Tuple

import numpy as np

MADD = "f_op_float32_madd"
ADD = "f_op_float32_add"
LOAD = "f_mem_contig_float32_load"
STORE = "f_mem_contig_float32_store"
LAUNCH = "f_sync_launch_kernel"
FEATURES = (MADD, ADD, LOAD, STORE, LAUNCH)

_ADD_PRIMS = {"add", "add_any", "sub", "neg", "cumsum", "abs"}
_ADD_REDUCE = {"reduce_sum", "reduce_and", "reduce_or"}
_CONTIG = {"broadcast_in_dim", "pad", "slice", "squeeze", "expand_dims",
           "copy", "convert_element_type", "reshape", "iota", "select_n"}


def _n(aval) -> int:
    return int(np.prod(aval.shape)) if aval.shape else 1


def _f32(aval) -> bool:
    return str(aval.dtype) == "float32"


def _sub(params: Mapping[str, Any]):
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = params.get(key)
        if sub is not None:
            return getattr(sub, "jaxpr", sub), tuple(getattr(sub, "consts",
                                                             ()))
    return None, ()


class _Walk:
    """Accumulates the five features over one jaxpr tree."""

    def __init__(self):
        self.c = {f: 0.0 for f in FEATURES}

    def add(self, f: str, v: float) -> None:
        self.c[f] += float(v)

    def jaxpr(self, jx, mult: float, body: "_Kernel" = None) -> None:
        for eqn in jx.eqns:
            self.eqn(eqn, mult, body)

    def eqn(self, eqn, mult: float, body: "_Kernel" = None) -> None:
        prim = eqn.primitive.name
        out = eqn.outvars[0].aval if eqn.outvars else None
        if body is not None and body.claims(eqn, self, mult):
            return
        if prim == "pallas_call":
            _Kernel(eqn).charge(self, mult)
        elif prim == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            k = int(np.prod([eqn.invars[0].aval.shape[d] for d in lc]))
            if _f32(out):
                self.add(MADD, _n(out) * k * mult)
            if body is None:
                for v in eqn.invars:
                    if _f32(v.aval):
                        self.add(LOAD, _n(v.aval) * mult)
                if _f32(out):
                    self.add(STORE, _n(out) * mult)
        elif prim in _ADD_PRIMS:
            if _f32(out):
                self.add(ADD, _n(out) * mult)
        elif prim in _ADD_REDUCE:
            if _f32(eqn.invars[0].aval):
                self.add(ADD, _n(eqn.invars[0].aval) * mult)
        elif prim in _CONTIG:
            if body is None and _f32(out):
                self.add(STORE, _n(out) * mult)
        elif prim == "addupdate":
            if _f32(eqn.invars[1].aval):
                self.add(ADD, _n(eqn.invars[1].aval) * mult)
        elif prim == "scan":
            self.jaxpr(eqn.params["jaxpr"].jaxpr,
                       mult * eqn.params["length"], body)
        elif prim == "while":
            self.jaxpr(eqn.params["body_jaxpr"].jaxpr, mult, body)
            self.jaxpr(eqn.params["cond_jaxpr"].jaxpr, mult, body)
        elif prim == "cond":
            br = eqn.params["branches"]
            for b in br:
                self.jaxpr(b.jaxpr, mult / len(br), body)
        else:
            jx, _ = _sub(eqn.params)
            if jx is not None:
                self.jaxpr(jx, mult, body)


class _Kernel:
    """One ``pallas_call``: the grid, its ``ANY``-space refs, and the scalar
    values derived from ``program_id`` at every grid program."""

    def __init__(self, eqn):
        self.eqn = eqn
        gm = eqn.params["grid_mapping"]
        self.gm = gm
        self.grid = tuple(int(g) for g in gm.grid)
        self.programs = int(np.prod(self.grid)) if self.grid else 1
        idx = np.indices(self.grid, dtype=np.int64) if self.grid else None
        self.axes = [a.reshape(-1) for a in idx] if self.grid else []
        body = eqn.params["jaxpr"]
        self.body = body
        n_ops = gm.num_inputs + gm.num_outputs
        self.refs = body.invars[:n_ops]
        self.any = {id(v) for v in self.refs if _any_space(v.aval)}
        self.env: Dict[int, np.ndarray] = {}
        self.live = [np.ones(max(self.programs, 1), bool)]

    def _val(self, v):
        if hasattr(v, "val"):                  # a literal
            return np.asarray(v.val)
        return self.env.get(id(v))

    def claims(self, eqn, walk: _Walk, mult: float) -> bool:
        prim = eqn.primitive.name
        if prim in ("get", "swap", "addupdate"):
            ref = eqn.invars[0]
            if id(ref) not in self.any and not _any_space(ref.aval):
                return False
            if not _f32(ref.aval):
                return True
            if prim == "get":
                walk.add(LOAD, _n(eqn.outvars[0].aval) * mult)
            elif prim == "swap":
                walk.add(STORE, _n(eqn.outvars[0].aval) * mult)
            else:
                walk.add(LOAD, _n(eqn.invars[1].aval) * mult)
                walk.add(STORE, _n(eqn.invars[1].aval) * mult)
            return True
        if prim == "program_id":
            if self.axes:
                self.env[id(eqn.outvars[0])] = self.axes[eqn.params["axis"]]
            return False
        if prim == "num_programs":
            self.env[id(eqn.outvars[0])] = np.asarray(
                self.grid[eqn.params["axis"]], np.int64)
            return False
        if prim == "cond":
            sel = self._val(eqn.invars[0])
            if sel is None:
                return False                    # averaged by the walk
            branches = eqn.params["branches"]
            live = self.live[-1]
            sel = np.broadcast_to(np.clip(sel.astype(np.int64), 0,
                                          len(branches) - 1), live.shape)
            for b, br in enumerate(branches):
                mask = live & (sel == b)
                if not mask.any():
                    continue
                for var, outer in zip(br.jaxpr.invars, eqn.invars[1:]):
                    val = self._val(outer)
                    if val is not None:
                        self.env[id(var)] = val
                self.live.append(mask)
                walk.jaxpr(br.jaxpr, mult * mask.sum() / live.sum(), self)
                self.live.pop()
            return True
        jx, _ = _sub(eqn.params)
        if jx is not None:
            for var, outer in zip(jx.invars, eqn.invars):
                val = self._val(outer)
                if val is not None:
                    self.env[id(var)] = val
            return False
        # scalar integer arithmetic on grid indices, evaluated at every
        # grid program at once
        if len(eqn.outvars) == 1 and eqn.outvars[0].aval.shape == () \
                and eqn.invars:
            vals = [self._val(v) for v in eqn.invars]
            if all(v is not None for v in vals):
                r = _scalar(prim, vals, eqn.params)
                if r is not None:
                    self.env[id(eqn.outvars[0])] = r
        return False

    def charge(self, walk: _Walk, mult: float) -> None:
        inner = _Walk()
        inner.jaxpr(self.body, 1.0, self)
        for f, v in inner.c.items():
            walk.add(f, v * self.programs * mult)
        n_in = self.gm.num_inputs
        for pos, bm in enumerate(self.gm.block_mappings):
            if pos < len(self.refs) and id(self.refs[pos]) in self.any:
                continue
            if str(bm.array_aval.dtype) != "float32":
                continue
            blocks = _block_indices(bm.index_map_jaxpr, self.axes)
            fetches = len(blocks) if len(blocks) <= 1 else int(
                np.any(blocks[1:] != blocks[:-1], axis=1).sum()) + 1
            elems = 1
            for b in bm.block_shape:
                size = getattr(b, "block_size", b)
                if isinstance(size, (int, np.integer)):
                    elems *= int(size)
            walk.add(LOAD if pos < n_in else STORE, fetches * elems * mult)


def _any_space(aval) -> bool:
    ms = getattr(aval, "memory_space", None)
    return getattr(ms, "value", None) == "any" if ms is not None else False


def _scalar(prim: str, v: List[np.ndarray], params) -> Any:
    """The integer scalar arithmetic that grid predicates are made of,
    with lax's truncating division."""
    if prim == "add":
        return v[0] + v[1]
    if prim == "sub":
        return v[0] - v[1]
    if prim == "mul":
        return v[0] * v[1]
    if prim == "div":
        q = np.abs(v[0]) // np.abs(v[1])
        return np.where((v[0] < 0) ^ (v[1] < 0), -q, q)
    if prim == "rem":
        return np.fmod(v[0], v[1])
    if prim in ("eq", "ne", "lt", "le", "gt", "ge"):
        return getattr(np, {"eq": "equal", "ne": "not_equal",
                            "lt": "less", "le": "less_equal",
                            "gt": "greater",
                            "ge": "greater_equal"}[prim])(v[0], v[1])
    if prim == "and":
        return v[0] & v[1]
    if prim == "or":
        return v[0] | v[1]
    if prim == "not":
        return ~v[0]
    if prim == "convert_element_type":
        return v[0].astype(np.int64)
    if prim == "select_n":
        return np.choose(v[0].astype(np.int64), v[1:])
    if prim in ("max", "min"):
        return getattr(np, "maximum" if prim == "max" else "minimum")(*v)
    return None


def _block_indices(closed, axes: List[np.ndarray]) -> np.ndarray:
    """The block index of one operand at every grid program, in grid
    order, from its index map evaluated on the host."""
    import jax

    n = axes[0].shape[0] if axes else 1
    with jax.default_device(jax.devices("cpu")[0]):
        if axes:
            outs = jax.vmap(lambda *i: jax.core.eval_jaxpr(
                closed.jaxpr, closed.consts, *i))(*[np.asarray(a, np.int32)
                                                    for a in axes])
        else:
            outs = jax.core.eval_jaxpr(closed.jaxpr, closed.consts)
    cols = [np.broadcast_to(np.asarray(o, np.int64).reshape(-1), (n,))
            for o in outs]
    return np.stack(cols, axis=1) if cols else np.zeros((n, 0), np.int64)


def count(fn, args: Sequence[Any]) -> Dict[str, float]:
    """The five priced features of ``fn`` at ``args`` (arrays or
    ``ShapeDtypeStruct``s)."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    walk = _Walk()
    walk.jaxpr(closed.jaxpr, 1.0)
    walk.add(LAUNCH, 1.0)
    return walk.c


# ---------------------------------------------------------------------------
# the rungs, written out
# ---------------------------------------------------------------------------

#: the rungs whose least-squares fit has one optimum (bounded linear least
#: squares), and their parameters; the overlap rung's objective is not
#: convex, so no reference fit of it is unique and only its prices are
#: compared
LINEAR: Dict[str, Tuple[str, ...]] = {
    "lin_flop": ("p_madd", "p_launch"),
    "lin_flop_mem": ("p_madd", "p_mem", "p_launch"),
}


def price(rung: str, params: Mapping[str, float],
          counts: Mapping[str, np.ndarray], xp=np, dtype=np.float64):
    """Seconds under ``rung``: flop cost, memory cost over contiguous
    float32 loads, stores and adds, and a launch cost; the overlap rung
    takes the two costs as fully overlapped, each weighted by
    (tanh(p_edge (a - b) / (a + b)) + 1) / 2."""
    c = {f: xp.asarray(counts[f], dtype) for f in FEATURES}
    p = {k: xp.asarray(v, dtype) for k, v in params.items()}
    flop = p["p_madd"] * c[MADD]
    launch = p["p_launch"] * c[LAUNCH]
    if rung == "lin_flop":
        return flop + launch
    mem = p["p_mem"] * (c[LOAD] + c[STORE] + c[ADD])
    if rung == "lin_flop_mem":
        return flop + mem + launch
    tot = xp.abs(flop) + xp.abs(mem)
    safe = xp.where(tot > 0, tot, xp.asarray(1.0, dtype))
    half = xp.asarray(0.5, dtype)
    both = flop * (xp.tanh(p["p_edge"] * (flop - mem) / safe) + 1) * half \
        + mem * (xp.tanh(p["p_edge"] * (mem - flop) / safe) + 1) * half
    return both + launch


def holdout(names: Sequence[str], fraction: float = 0.25,
            salt: str = "holdout") -> Tuple[List[int], List[int]]:
    """(train, held-out) row indices: rows ranked by the SHA-256 of
    ``salt:name``, the lowest ``round(fraction * n)`` held out."""
    def rank(name):
        h = hashlib.sha256(f"{salt}:{name}".encode()).hexdigest()[:12]
        return int(h, 16), name

    order = sorted(range(len(names)), key=lambda i: rank(names[i]))
    k = min(max(int(round(fraction * len(names))), 1), len(names) - 1)
    return sorted(order[k:]), sorted(order[:k])


def cost(rung: str, params: Mapping[str, float],
         counts: Mapping[str, np.ndarray], seconds: np.ndarray) -> float:
    """Sum of squared relative errors over the rows: the fit's objective."""
    r = 1.0 - price(rung, params, counts) / np.asarray(seconds, np.float64)
    return float(np.sum(r * r))


def fit(rung: str, counts: Mapping[str, np.ndarray], seconds: np.ndarray
        ) -> Dict[str, float]:
    """Least relative squared error of a linear rung in float64, every
    parameter kept >= 0: bounded linear least squares, solved exactly."""
    from scipy.optimize import lsq_linear

    names = LINEAR[rung]
    t = np.asarray(seconds, np.float64)
    cols = {"p_madd": counts[MADD], "p_launch": counts[LAUNCH],
            "p_mem": np.asarray(counts[LOAD]) + counts[STORE] + counts[ADD]}
    A = np.stack([np.asarray(cols[n], np.float64) / t for n in names], 1)
    scale = np.maximum(np.abs(A).max(axis=0), 1e-300)
    res = lsq_linear(A / scale, np.ones_like(t), bounds=(0, np.inf),
                     method="bvls", tol=1e-15)
    return {n: float(x) for n, x in zip(names, res.x / scale)}
