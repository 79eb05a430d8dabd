"""Perflex-style cost models: user-written arithmetic expressions over
kernel *features* (``f_*``) and machine *parameters* (``p_*``).

  model = Model("f_wall_time_cpu_host",
                "p_f32madd * f_op_float32_madd + "
                "p_membw * (f_mem_contig_float32_load "
                "           + f_mem_contig_float32_store)")

Expressions are parsed with Python's ``ast`` into a safe, differentiable
jax-numpy evaluator — so a model can be arbitrarily nonlinear (the overlap
model of §7.4 uses ``smooth_step``), and calibration gets exact Jacobians
via autodiff instead of the paper's symbolic differentiation.

The evaluator is compiled ONCE per model and is fully vectorized: features
enter as columns of a dense ``[n_rows, n_features]`` matrix (see
:class:`FeatureTable`), parameters as a flat vector, and every measurement
row is evaluated in one traced expression.  That makes the whole
calibration pipeline (``repro.core.calibrate``) jit-compilable with no
per-row Python dispatch.
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core import overlap as _ovl
from repro.core.counting import FeatureCounts
from repro.deprecation import warn_once

_FUNCS: Dict[str, Callable] = {
    "smooth_step": _ovl.smooth_step,
    "overlap2": _ovl.overlap2,
    "overlap2_raw": _ovl.overlap2_raw,
    "overlap3": _ovl.overlap3,
    "smoothmax": lambda *a: _ovl.smoothmax(a[:-1], a[-1]),
    "partial_overlap2": _ovl.partial_overlap2,
    "exp": jnp.exp, "log": jnp.log, "tanh": jnp.tanh, "sqrt": jnp.sqrt,
    "maximum": jnp.maximum, "minimum": jnp.minimum, "abs": jnp.abs,
}

_ALLOWED_NODES = (
    ast.Expression, ast.BinOp, ast.UnaryOp, ast.Call, ast.Name, ast.Load,
    ast.Constant, ast.Add, ast.Sub, ast.Mult, ast.Div, ast.Pow, ast.USub,
    ast.UAdd, ast.Tuple,
)


def _parse(expr: str) -> ast.Expression:
    tree = ast.parse(expr, mode="eval")
    for node in ast.walk(tree):
        if not isinstance(node, _ALLOWED_NODES):
            raise ValueError(f"disallowed syntax in model expression: "
                             f"{ast.dump(node)[:60]}")
        if isinstance(node, ast.Call):
            if not isinstance(node.func, ast.Name) or \
                    node.func.id not in _FUNCS:
                raise ValueError(f"unknown function in model: "
                                 f"{getattr(node.func, 'id', '?')}")
    return tree


def _names(tree: ast.Expression) -> List[str]:
    return sorted({n.id for n in ast.walk(tree)
                   if isinstance(n, ast.Name) and n.id not in _FUNCS})


# cost-combining functions whose value can be attributed back to their
# leading cost arguments (the paper's "cost-explanatory" requirement for
# nonlinear models): function name → how many leading arguments are costs.
# ``None`` means all-but-the-last argument (smoothmax's variadic tuple).
_ATTRIBUTABLE_CALLS: Dict[str, Optional[int]] = {
    "overlap2": 2, "overlap2_raw": 2, "overlap3": 3,
    "partial_overlap2": 2, "smoothmax": None,
}


def _signed_terms(node: ast.expr, sign: float = 1.0):
    """Split an expression at top-level +/- into (sign, term-node) pairs."""
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        yield from _signed_terms(node.left, sign)
        yield from _signed_terms(node.right, sign)
    elif isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub):
        yield from _signed_terms(node.left, sign)
        yield from _signed_terms(node.right, -sign)
    elif isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        yield from _signed_terms(node.operand, -sign)
    elif isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.UAdd):
        yield from _signed_terms(node.operand, sign)
    else:
        yield sign, node


def _compile_node(node: ast.expr):
    expr = ast.Expression(body=node)
    ast.fix_missing_locations(expr)
    return compile(expr, "<perflex-term>", "eval")


def _param_dtype():
    return jnp.float64 if jax.config.read("jax_enable_x64") else jnp.float32


# Process-wide cache of the compiled programs built from a model (the
# Jacobian of :meth:`Model.param_jacobian`, calibration's LM solver),
# keyed by model *content signature* + program key.  Each Model caches its
# programs locally, but a study recreates Model objects every profile (zoo
# registry, profile loads) — identical (output feature, expr) must not pay
# re-tracing, so programs are shared across instances here.  Sound because
# the signature pins the exact expression, hence identical param/feature
# orderings and identical computations.  FIFO-bounded: each compiled
# closure pins a Model for as long as it is cached, and a long-lived
# process sweeping many distinct expressions must not grow without bound.
_SHARED_COMPILED: Dict[tuple, Callable] = {}
_SHARED_COMPILED_MAX = 128


# ---------------------------------------------------------------------------
# Dense feature-matrix representation of a measurement table
# ---------------------------------------------------------------------------


@dataclass
class FeatureTable:
    """A measurement table as a dense ``[n_rows, n_features]`` matrix.

    ``feature_ids`` names the columns; ``row_names`` carries the measurement
    kernel behind each row (bookkeeping, ignored by models).  This is the
    native input of the batched calibration pipeline; a list of per-row
    dicts (the original representation) is still accepted everywhere and
    converted via :meth:`from_rows`.
    """

    feature_ids: List[str]
    values: np.ndarray                      # [n_rows, n_features] float64
    row_names: List[str] = field(default_factory=list)
    # per-row measurement-noise metadata keyed by row name, e.g.
    # {"median": ..., "std": ..., "min": ...} — populated by
    # gather_feature_table when the timer reports spread, empty otherwise
    row_noise: Dict[str, Dict[str, float]] = field(default_factory=dict)

    def __post_init__(self):
        self.values = np.asarray(self.values, np.float64)
        if self.values.ndim != 2 or \
                self.values.shape[1] != len(self.feature_ids):
            raise ValueError(
                f"values must be [n_rows, {len(self.feature_ids)}], "
                f"got {self.values.shape}")
        self._col = {f: i for i, f in enumerate(self.feature_ids)}
        if not self.row_names:
            self.row_names = [f"row{i}" for i in range(len(self.values))]
        # transient gather provenance (NOT serialized, not carried through
        # select): names of rows the noisy-row heuristic re-timed — see
        # gather_feature_table(retime_rel_std=...)
        self.retimed_rows: List[str] = []

    def __len__(self) -> int:
        return self.values.shape[0]

    def column(self, feature_id: str) -> np.ndarray:
        """Column vector for one feature; zeros if the feature is absent
        (missing features read as 0, matching ``FeatureCounts``)."""
        j = self._col.get(feature_id)
        if j is None:
            return np.zeros((len(self),), np.float64)
        return self.values[:, j]

    def row(self, i: int) -> Dict[str, float]:
        d = {f: float(self.values[i, j]) for f, j in self._col.items()}
        d["_kernel"] = self.row_names[i]
        return d

    def rows(self) -> List[Dict[str, float]]:
        """Dict-per-row view (compatibility with the original API)."""
        return [self.row(i) for i in range(len(self))]

    @classmethod
    def from_rows(cls, rows: Sequence[Mapping[str, float]]) -> "FeatureTable":
        ids = sorted({k for r in rows for k in r if not k.startswith("_")})
        vals = np.zeros((len(rows), len(ids)), np.float64)
        for i, r in enumerate(rows):
            for j, f in enumerate(ids):
                vals[i, j] = float(r.get(f, 0.0))
        names = [str(r.get("_kernel", f"row{i}")) for i, r in enumerate(rows)]
        return cls(ids, vals, names)

    def select(self, indices: Sequence[int]) -> "FeatureTable":
        """Sub-table of the given rows (noise metadata follows its rows)."""
        idx = list(indices)
        names = [self.row_names[i] for i in idx]
        return FeatureTable(
            list(self.feature_ids), self.values[idx, :], names,
            {n: dict(self.row_noise[n]) for n in names
             if n in self.row_noise})

    def noise_summary(self) -> Dict[str, float]:
        """Relative wall-clock noise (std / median) summary over rows that
        carry spread metadata; empty when none do.  The single source of
        the fit-diagnostic noise line (CLI) and report noise section."""
        rel = [d["std"] / d["median"] for d in self.row_noise.values()
               if d.get("std") is not None and d.get("median", 0) > 0]
        if not rel:
            return {}
        return {"max_rel_std": float(np.max(rel)),
                "median_rel_std": float(np.median(rel)),
                "rows": float(len(rel))}

    # -- JSON round trip (profile holdout persistence) -----------------------
    def to_dict(self) -> Dict[str, object]:
        return {
            "feature_ids": list(self.feature_ids),
            "values": [[float(v) for v in row] for row in self.values],
            "row_names": list(self.row_names),
            "row_noise": {n: {k: float(v) for k, v in d.items()}
                          for n, d in sorted(self.row_noise.items())},
        }

    @classmethod
    def from_dict(cls, d: Mapping[str, object]) -> "FeatureTable":
        return cls(
            [str(f) for f in d["feature_ids"]],
            np.asarray(d["values"], np.float64).reshape(
                len(d["row_names"]), len(d["feature_ids"])),
            [str(n) for n in d["row_names"]],
            {str(n): {str(k): float(v) for k, v in dict(nd).items()}
             for n, nd in dict(d.get("row_noise", {})).items()})


FeatureTableLike = Union[FeatureTable, Sequence[Mapping[str, float]]]


def as_feature_table(table: FeatureTableLike) -> FeatureTable:
    if isinstance(table, FeatureTable):
        return table
    return FeatureTable.from_rows(table)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


@dataclass
class Model:
    """output feature ≈ g(input features; parameters)."""

    output_feature: str
    expr: str

    def __post_init__(self):
        self._tree = _parse(self.expr)
        names = _names(self._tree)
        self.param_names: List[str] = [n for n in names if n.startswith("p_")]
        self.feature_names: List[str] = [n for n in names if n.startswith("f_")]
        bad = [n for n in names if not n.startswith(("p_", "f_"))]
        if bad:
            raise ValueError(f"model names must start with p_/f_: {bad}")
        code = compile(self._tree, "<perflex-model>", "eval")

        def evaluator(env: Mapping[str, jax.Array]):
            return eval(code, {"__builtins__": {}}, {**_FUNCS, **env})

        self._eval = evaluator
        # compiled programs of this model, keyed as in :meth:`compiled`
        self._compiled: Dict[tuple, Callable] = {}
        # per-term breakdown plan, built lazily on first breakdown request
        self._breakdown_plan: Optional[List[tuple]] = None

    # -- feature bookkeeping ------------------------------------------------
    def all_features(self) -> List[str]:
        return [self.output_feature, *self.feature_names]

    def signature(self) -> str:
        """Stable content identity of this model (output feature + expr).

        Machine profiles store fitted parameters under this signature so a
        loaded fit can be matched to the model it was calibrated for, and
        silent expression drift surfaces as a clear lookup error instead of
        nonsense predictions."""
        import hashlib
        h = hashlib.sha256(
            f"{self.output_feature}\n{self.expr}".encode()).hexdigest()
        return h[:16]

    # -- evaluation ---------------------------------------------------------
    def evaluate(self, param_values: Mapping[str, float],
                 feature_values: Mapping[str, float]):
        env = {n: jnp.asarray(param_values[n]) for n in self.param_names}
        env.update({n: jnp.asarray(float(feature_values.get(n, 0.0)))
                    for n in self.feature_names})
        return self._eval(env)

    def eval_with_counts(self, param_values: Mapping[str, float],
                         counts: FeatureCounts):
        """Deprecated: use :meth:`align` + :meth:`batched_eval`, or the
        :class:`repro.api.PerfSession` facade."""
        warn_once(
            "Model.eval_with_counts",
            "Model.eval_with_counts is deprecated; use Model.align + "
            "Model.batched_eval, or repro.api.PerfSession.predict")
        return float(self.evaluate(param_values, counts))

    # -- feature alignment --------------------------------------------------
    def align(self, counts: Union[FeatureTableLike, Mapping[str, float]],
              *, missing: str = "error") -> np.ndarray:
        """Align feature values against this model: a dense
        ``[n_rows, n_features]`` float64 matrix with columns ordered as
        ``self.feature_names`` — the one sanctioned bridge from counted
        kernels to :meth:`batched_eval`/:meth:`batched_breakdown`.

        ``counts`` may be a single :class:`FeatureCounts`-like mapping, a
        sequence of them (one row each), or a gathered
        :class:`FeatureTable`.  Mappings follow counts semantics: a feature
        the counter never produced is genuinely zero.  For a
        ``FeatureTable`` the ``missing`` policy applies to absent columns:
        ``"error"`` (default) raises ``ValueError`` naming them — a
        gathered table lacking a column means the feature was never
        measured, and silently reading 0 fabricates predictions —
        while ``"zero"`` keeps the legacy zero-fill behavior.
        """
        if missing not in ("error", "zero"):
            raise ValueError(f"missing must be 'error' or 'zero', "
                             f"got {missing!r}")
        if isinstance(counts, Mapping):
            counts = [counts]
        if isinstance(counts, FeatureTable):
            absent = [n for n in self.feature_names
                      if n not in counts.feature_ids]
            if absent and missing == "error":
                raise ValueError(
                    f"feature table lacks columns {absent} required by the "
                    f"{self.output_feature!r} model (alignment would "
                    f"silently read them as 0) — re-gather with these "
                    f"features")
            if not self.feature_names:
                return np.zeros((len(counts), 0), np.float64)
            return np.stack([counts.column(n) for n in self.feature_names],
                            axis=1)
        rows = list(counts)
        out = np.zeros((len(rows), len(self.feature_names)), np.float64)
        for i, r in enumerate(rows):
            for j, n in enumerate(self.feature_names):
                out[i, j] = float(r.get(n, 0.0))
        return out

    def unmodeled_features(self, counts: Mapping[str, float]
                           ) -> Dict[str, float]:
        """Nonzero counted features this model has NO term for — the scope
        diagnostic behind the facade's strict-scope prediction mode (work
        the kernel performs that the model cannot attribute a cost to)."""
        known = set(self.feature_names)
        known.add(self.output_feature)
        return {k: float(v) for k, v in sorted(counts.items())
                if k not in known and not k.startswith("_") and float(v)}

    def param_feature_map(self) -> Dict[str, List[str]]:
        """Which features each parameter multiplies: parameter name → the
        sorted feature names appearing in the same top-level additive
        terms.  Two parameters sharing an identical feature list are
        *structurally* suspect (their design-matrix columns can only
        differ through nonlinearity) — the identifiability analyzer uses
        this to NAME the features behind a collinear parameter pair
        instead of just reporting an abstract rank defect."""
        out: Dict[str, set] = {p: set() for p in self.param_names}
        for _sign, node in _signed_terms(self._tree.body):
            names = {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}
            feats = {n for n in names if n.startswith("f_")}
            for p in names:
                if p.startswith("p_"):
                    out[p] |= feats
        return {p: sorted(fs) for p, fs in out.items()}

    def compiled(self, key: tuple, build: Callable[[], Callable]
                 ) -> Tuple[Callable, bool]:
        """``(program, built)``: the program ``build()`` makes for this
        model under ``key``, taken from this instance, else from the
        process-wide cache of equal models (same :meth:`signature`), else
        built now and put in both.  ``built`` says which; ``jax.jit``
        itself still re-specializes on new argument shapes."""
        fn = self._compiled.get(key)
        if fn is not None:
            return fn, False
        shared_key = (self.signature(),) + key
        fn = _SHARED_COMPILED.get(shared_key)
        built = fn is None
        if built:
            fn = build()
            while len(_SHARED_COMPILED) >= _SHARED_COMPILED_MAX:
                _SHARED_COMPILED.pop(next(iter(_SHARED_COMPILED)))
            _SHARED_COMPILED[shared_key] = fn
        self._compiled[key] = fn
        return fn, built

    def param_jacobian(self, points: np.ndarray, features: np.ndarray
                       ) -> np.ndarray:
        """``∂ prediction / ∂ parameters`` at each of ``k`` parameter
        points (``points`` is ``[k, n_params]``): ``[k, n_rows, n_params]``
        float64, rows aligned with ``features`` (same column conventions
        as :meth:`batched_eval`), columns ordered as ``self.param_names``.
        Block ``i`` IS the least-squares design matrix of a fit linearized
        at ``points[i]`` — exact for linear models at any point — and the
        raw material of the static identifiability analysis
        (``repro.analysis.identifiability``).

        One compiled ``jit(vmap(jacfwd(batched_eval)))`` call and one host
        fetch, evaluated in :func:`_param_dtype`.  The program is cached by
        :meth:`compiled`, so equal models re-created each profile reuse
        it; each reuse adds 1 to the ``reused`` attr of the innermost open
        span (``repro.spans``)."""
        fn, built = self.compiled(("param_jacobian",), lambda: jax.jit(
            jax.vmap(jax.jacfwd(self.batched_eval), in_axes=(0, None))))
        if not built:
            spans.add("reused")
        dt = np.dtype(_param_dtype())
        J = fn(np.asarray(points, dt), np.asarray(features, dt))
        return np.asarray(J, np.float64)

    def batched_eval(self, p_vec: jax.Array, features: jax.Array
                     ) -> jax.Array:
        """Vectorized evaluation: ``features`` is ``[n_rows, n_features]``
        with columns ordered as ``self.feature_names``; returns ``[n_rows]``
        predictions.  Trace-safe: one jnp expression over whole columns."""
        env: Dict[str, jax.Array] = {
            n: p_vec[i] for i, n in enumerate(self.param_names)}
        env.update({n: features[:, j]
                    for j, n in enumerate(self.feature_names)})
        out = self._eval(env)
        # constant-only expressions broadcast to one value per row
        return jnp.broadcast_to(out, (features.shape[0],))

    # -- cost-explanatory per-term breakdown --------------------------------
    def _plan(self) -> List[tuple]:
        """Lazily-built breakdown plan: the expression split at top-level
        +/- into signed terms, each compiled separately; attributable
        nonlinear calls (overlap2 & co) additionally carry compiled
        evaluators for their cost arguments so their value can be split
        back into per-component contributions."""
        if self._breakdown_plan is None:
            plan = []
            for sign, node in _signed_terms(self._tree.body):
                prefix = "-" if sign < 0 else ""
                label = prefix + ast.unparse(node)
                comps = None
                if isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Name) \
                        and node.func.id in _ATTRIBUTABLE_CALLS:
                    k = _ATTRIBUTABLE_CALLS[node.func.id]
                    if k is None:
                        k = len(node.args) - 1
                    if 2 <= k <= len(node.args):
                        comps = [(f"{prefix}{node.func.id}"
                                  f"[{ast.unparse(a)}]", _compile_node(a))
                                 for a in node.args[:k]]
                plan.append((sign, label, _compile_node(node), comps))
            self._breakdown_plan = plan
        return self._breakdown_plan

    @property
    def breakdown_labels(self) -> List[str]:
        """Column labels of :meth:`batched_breakdown`, in order."""
        labels: List[str] = []
        for _sign, label, _code, comps in self._plan():
            if comps is None:
                labels.append(label)
            else:
                labels.extend(cl for cl, _ in comps)
        return labels

    def batched_breakdown(self, p_vec: jax.Array, features: jax.Array
                          ) -> jax.Array:
        """Per-term cost contributions: ``[n_rows, n_parts]`` with columns
        labeled by :attr:`breakdown_labels` — the paper's cost-explanatory
        attribute as data.  Row sums equal the model's predicted value by
        construction: top-level additive terms are evaluated separately,
        and an attributable nonlinear term (e.g. ``overlap2``) is split
        into per-component parts proportional to its component costs, with
        the LAST part computed as the term value minus the others so the
        split is exact, not approximate.  Trace-safe; same column
        conventions as :meth:`batched_eval`.
        """
        env: Dict[str, jax.Array] = {
            n: p_vec[i] for i, n in enumerate(self.param_names)}
        env.update({n: features[:, j]
                    for j, n in enumerate(self.feature_names)})
        ns = {**_FUNCS, **env}
        scope = {"__builtins__": {}}
        n_rows = features.shape[0]
        cols: List[jax.Array] = []
        for sign, _label, code, comps in self._plan():
            v = eval(code, scope, ns)
            if sign != 1.0:
                v = v * sign
            v = jnp.broadcast_to(v, (n_rows,))
            if comps is None:
                cols.append(v)
                continue
            cvals = [jnp.broadcast_to(jnp.abs(eval(c_code, scope, ns)),
                                      (n_rows,))
                     for _cl, c_code in comps]
            tot = cvals[0]
            for c in cvals[1:]:
                tot = tot + c
            safe = jnp.where(tot > 0, tot, 1.0)
            acc = jnp.zeros_like(v)
            for c in cvals[:-1]:
                part = v * jnp.where(tot > 0, c / safe, 1.0 / len(cvals))
                cols.append(part)
                acc = acc + part
            cols.append(v - acc)
        return jnp.stack(cols, axis=1)

    # -- design matrix ------------------------------------------------------
    def design_matrix(self, table: FeatureTableLike,
                      *, scale_by_output: bool = True
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """``(F, target)`` for least-squares: ``F`` is ``[n_rows, n_feat]``
        in ``self.feature_names`` column order, ``target`` the per-row fit
        target.  With ``scale_by_output`` (paper §7.2) each row is divided
        by its measured output value — a relative-error fit with target 1.
        """
        ft = as_feature_table(table)
        if self.output_feature not in ft.feature_ids:
            raise KeyError(
                f"output feature {self.output_feature!r} not present in the "
                f"feature table (columns: {ft.feature_ids})")
        t = ft.column(self.output_feature)
        # legacy zero-fill: fitting tolerates never-gathered columns (the
        # strict path is Model.align's default, used by the facade)
        F = self.align(ft, missing="zero")
        if scale_by_output:
            bad = np.flatnonzero(~(t > 0))
            if bad.size:
                i = int(bad[0])
                raise ValueError(
                    f"output feature {self.output_feature!r} must be "
                    f"positive to scale rows by it; row {i} "
                    f"({ft.row_names[i]!r}) has value {t[i]!r}")
            F = F / t[:, None]
            target = np.ones_like(t)
        else:
            target = t
        return F, target

    # -- residual builder for calibration -----------------------------------
    def residual_fn(self, feature_table: FeatureTableLike,
                    *, scale_by_output: bool = True):
        """Returns (resid(p_vec) -> r[k], p0, param_names).

        ``feature_table``: a :class:`FeatureTable` or one dict per
        measurement kernel mapping feature id → value, including the output
        feature.  The residual closes over constant on-device arrays and is
        a single vectorized expression — jit/vmap/jacfwd-friendly.
        """
        F_np, target_np = self.design_matrix(
            feature_table, scale_by_output=scale_by_output)
        dt = _param_dtype()
        F = jnp.asarray(F_np, dt)
        target = jnp.asarray(target_np, dt)

        def resid(p_vec: jax.Array) -> jax.Array:
            return target - self.batched_eval(p_vec, F)

        p0 = jnp.full((len(self.param_names),), 1e-9, dt)
        return resid, p0, self.param_names
