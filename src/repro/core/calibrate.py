"""Black-box model calibration (paper §7.2): nonlinear least squares via
Levenberg-Marquardt, implemented in JAX (autodiff Jacobians, jnp linear
algebra) rather than scipy — so calibration itself is jit-able and the same
code runs on CPU or TPU.

The fit minimizes ‖t − g(p)‖₂ over parameters p, one residual row per
measurement kernel; with ``scale_features_by_output`` (default, as in all
the paper's experiments) rows are normalized by the measured output, making
it a relative-error fit.

The solver is a single jit-compiled ``lax.while_loop``: the Jacobian
(``jax.jacfwd``) is traced once, the inner damping search runs inside the
trace, and multi-start restarts are ``vmap``-ed so all seeds solve in one
compiled call with no host syncs until the final result fetch.  Compiled
solvers are cached per ``Model`` (keyed by solver options), so repeated
calibrations — per machine, per model variant — pay tracing once.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import (
    Callable, Collection, Dict, Mapping, Optional, Sequence, Tuple, Union,
)

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core.model import (
    FeatureTableLike,
    Model,
    _param_dtype,
    as_feature_table,
)


@dataclass
class FitResult:
    params: Dict[str, float]
    residual_norm: float
    iterations: int
    converged: bool

    def __getitem__(self, k):
        return self.params[k]

    # -- (de)serialization, used by repro.profiles --------------------------
    def to_dict(self) -> Dict[str, object]:
        return {"params": dict(self.params),
                "residual_norm": self.residual_norm,
                "iterations": self.iterations,
                "converged": self.converged}

    @classmethod
    def from_dict(cls, d: Mapping[str, object]) -> "FitResult":
        return cls(params={str(k): float(v)
                           for k, v in dict(d["params"]).items()},
                   residual_norm=float(d["residual_norm"]),
                   iterations=int(d["iterations"]),
                   converged=bool(d["converged"]))


# ---------------------------------------------------------------------------
# Trace-friendly LM core
# ---------------------------------------------------------------------------


def _lm_core(
    resid_fn: Callable[[jax.Array], jax.Array],
    p0: jax.Array,
    *,
    max_iters: int,
    lam0: float,
    lam_up: float,
    lam_down: float,
    tol: float,
    nonneg: Union[bool, Tuple[bool, ...]],
    inner_tries: int = 20,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Classic LM with multiplicative damping adaptation, as one
    ``lax.while_loop`` — jit/vmap-safe, no host syncs.

    ``nonneg=True`` clamps parameters at 0 after each accepted step —
    the paper's cost-explanatory interpretability requirement (§4: negative
    per-operation costs are inconsistent with the notion of 'cost').  A
    per-parameter tuple of flags clamps only the flagged positions.

    Returns ``(p, cost, iterations, converged)`` as traced arrays.
    """
    jac = jax.jacfwd(resid_fn)
    dt = p0.dtype

    def attempt(p, cost, JTJ, JTr, diag, lam):
        """One damped solve + trial step at damping ``lam``.  Singular or
        ill-conditioned systems surface as non-finite ``dp`` from
        ``jnp.linalg.solve`` (it does not raise under jit), so acceptance
        requires finiteness explicitly."""
        A = JTJ + lam * jnp.diag(diag)
        dp = jnp.linalg.solve(A, -JTr)
        p_new = p + dp
        if nonneg is not False:
            p_new = jnp.where(jnp.asarray(nonneg), jnp.maximum(p_new, 0.0),
                              p_new)
        r_new = resid_fn(p_new)
        cost_new = jnp.sum(r_new * r_new)
        ok = (jnp.isfinite(dp).all() & jnp.isfinite(cost_new)
              & (cost_new < cost))
        return ok, p_new, r_new, cost_new

    def damping_search(p, r, cost, JTJ, JTr, lam):
        diag = jnp.maximum(jnp.diag(JTJ), jnp.asarray(1e-20, dt))

        def cond(s):
            tries, _, accepted, *_ = s
            return (~accepted) & (tries < inner_tries)

        def body(s):
            tries, lam, _, p_c, r_c, cost_c = s
            ok, p_n, r_n, cost_n = attempt(p, cost, JTJ, JTr, diag, lam)
            lam_n = jnp.where(ok,
                              jnp.maximum(lam * lam_down, 1e-12),
                              lam * lam_up)
            keep = lambda new, old: jnp.where(ok, new, old)
            return (tries + 1, lam_n.astype(dt), ok,
                    keep(p_n, p_c), keep(r_n, r_c), keep(cost_n, cost_c))

        return jax.lax.while_loop(
            cond, body,
            (jnp.int32(0), lam, jnp.bool_(False), p, r, cost))

    def outer_cond(s):
        p, r, cost, lam, it, converged, done = s
        return (~done) & (it < max_iters)

    def outer_body(s):
        p, r, cost, lam, it, converged, done = s
        J = jac(p)
        # full-f32 normal equations: a TPU runs default-precision f32
        # matmuls as bf16 passes, which would shift the fitted params
        JTJ = jnp.matmul(J.T, J, precision=jax.lax.Precision.HIGHEST)
        JTr = jnp.matmul(J.T, r, precision=jax.lax.Precision.HIGHEST)
        _, lam_n, accepted, p_c, r_c, cost_c = damping_search(
            p, r, cost, JTJ, JTr, lam)
        rel = (cost - cost_c) / jnp.maximum(cost, 1e-30)
        conv_now = accepted & (rel < tol)
        keep = lambda new, old: jnp.where(accepted, new, old)
        # damping exhausted without an acceptable step → local minimum
        return (keep(p_c, p), keep(r_c, r), keep(cost_c, cost), lam_n,
                it + 1, conv_now | ~accepted, conv_now | ~accepted)

    r0 = resid_fn(p0)
    cost0 = jnp.sum(r0 * r0)
    p, r, cost, lam, it, converged, done = jax.lax.while_loop(
        outer_cond, outer_body,
        (p0, r0, cost0, jnp.asarray(lam0, dt), jnp.int32(0),
         jnp.bool_(False), jnp.bool_(False)))
    return p, cost, it, converged


def levenberg_marquardt(
    resid_fn: Callable[[jax.Array], jax.Array],
    p0: jax.Array,
    *,
    max_iters: int = 200,
    lam0: float = 1e-3,
    lam_up: float = 10.0,
    lam_down: float = 0.3,
    tol: float = 1e-12,
    nonneg: bool = False,
) -> Tuple[jax.Array, float, int, bool]:
    """Single-start LM; one compiled call, one host fetch at the end."""
    p0 = jnp.asarray(p0, _param_dtype())
    solve = jax.jit(lambda p: _lm_core(
        resid_fn, p, max_iters=max_iters, lam0=lam0, lam_up=lam_up,
        lam_down=lam_down, tol=tol, nonneg=nonneg))
    p, cost, it, conv = solve(p0)
    return p, float(np.sqrt(float(cost))), int(it), bool(conv)


# ---------------------------------------------------------------------------
# Multi-start batched fit
# ---------------------------------------------------------------------------


def _batch_solver(model: Model, *, nonneg: Union[bool, Tuple[bool, ...]],
                  max_iters: int, lam0: float,
                  lam_up: float, lam_down: float, tol: float) -> Callable:
    """Compiled ``(F, target, starts) -> best (p, cost, it, conv)`` solver;
    cached by :meth:`Model.compiled`, so repeated calibrations — including
    of re-created equal models — re-use the trace (jit itself
    re-specializes on new table shapes)."""
    key = ("lm_batch", nonneg, max_iters, lam0, lam_up, lam_down, tol)

    def build():
        @jax.jit
        def solver(F, target, starts, scale):
            """``starts`` are in scale-normalized units: the model sees
            ``p_norm · scale``.  Normalizing by the nominal start makes the
            LM system well-conditioned when parameters span many orders of
            magnitude (rates ~1e-12 next to smoothing edges ~1e2 — float32
            cannot solve that system raw)."""
            def resid(p_norm):
                return target - model.batched_eval(p_norm * scale, F)

            def one(s):
                return _lm_core(resid, s, max_iters=max_iters, lam0=lam0,
                                lam_up=lam_up, lam_down=lam_down, tol=tol,
                                nonneg=nonneg)

            p, cost, it, conv = jax.vmap(one)(starts)
            best = jnp.argmin(cost)
            return p[best] * scale, cost[best], it[best], conv[best]

        return solver

    return model.compiled(key, build)[0]


def _multi_starts(p_init: jax.Array, names: Sequence[str], seeds: int
                  ) -> jax.Array:
    """``[seeds, n_params]`` deterministic restarts: the nominal start plus
    log-uniform perturbations (nonlinear overlap models have local minima).
    ``p_edge``-style parameters start at O(1), not O(1e-9)."""
    starts = [p_init]
    key = jax.random.PRNGKey(0)
    for _ in range(seeds - 1):
        key, sub = jax.random.split(key)
        starts.append(p_init * jnp.exp(
            jax.random.uniform(sub, p_init.shape, minval=-2.0, maxval=2.0)))
    out = jnp.stack(starts)
    edge_idx = [i for i, n in enumerate(names) if "edge" in n]
    if edge_idx:
        out = out.at[:, jnp.asarray(edge_idx, jnp.int32)].set(100.0)
    return out


def fit_model(
    model: Model,
    feature_table: FeatureTableLike,
    *,
    scale_by_output: bool = True,
    p0: Optional[Mapping[str, float]] = None,
    nonneg: Union[bool, Collection[str]] = False,
    seeds: int = 3,
    max_iters: int = 200,
    lam0: float = 1e-3,
    lam_up: float = 10.0,
    lam_down: float = 0.3,
    tol: float = 1e-12,
) -> FitResult:
    """Calibrate ``model`` against measurement-kernel feature rows.

    ``feature_table`` may be a :class:`repro.core.model.FeatureTable` or the
    original one-dict-per-row representation.  All restarts solve in a
    single compiled vmap-of-while-loop call; the best fit (lowest residual)
    is returned.  ``nonneg`` is ``True`` (every parameter is a cost, kept
    ≥ 0), ``False``, or the names of the parameters to keep ≥ 0.
    """
    table = as_feature_table(feature_table)
    F_np, target_np = model.design_matrix(
        table, scale_by_output=scale_by_output)
    names = model.param_names
    if not isinstance(nonneg, bool):
        nonneg = tuple(n in nonneg for n in names)
    dt = _param_dtype()

    p_init = jnp.full((len(names),), 1e-9, dt)
    if p0:
        p_init = jnp.asarray([p0.get(n, 1e-9) for n in names], dt)
    starts = _multi_starts(p_init, names, max(seeds, 1)).astype(dt)
    # LM runs in units where the nominal start is O(1) per parameter —
    # positions with a zero start keep raw units (scale 1)
    scale = jnp.where(starts[0] > 0, starts[0], 1.0).astype(dt)
    starts = starts / scale

    solver = _batch_solver(model, nonneg=nonneg, max_iters=max_iters,
                           lam0=lam0, lam_up=lam_up, lam_down=lam_down,
                           tol=tol)
    p, cost, it, conv = solver(jnp.asarray(F_np, dt),
                               jnp.asarray(target_np, dt), starts, scale)
    p = np.asarray(p)
    return FitResult(
        params={n: float(v) for n, v in zip(names, p)},
        residual_norm=float(np.sqrt(float(cost))),
        iterations=int(it), converged=bool(conv))


def fit_models(
    models: Mapping[str, Model],
    feature_table: FeatureTableLike,
    *,
    scale_by_output: bool = True,
    nonneg: Optional[Mapping[str, Union[bool, Collection[str]]]] = None,
    seeds: int = 3,
    warm_start: bool = True,
    **solver_opts,
) -> Dict[str, FitResult]:
    """Shared-table multi-fit: calibrate several named models over ONE
    gathered feature table (the paper's one-battery-many-fits workflow —
    every model form in a cross-machine study sees identical measurements,
    so accuracy differences are attributable to model scope, not noise).

    With ``warm_start`` (default), fits chain in ``models`` order: each
    model's nominal start is seeded with the parameter values already
    recovered by earlier (narrower-scope) fits for the names they share.
    This is what makes nonlinear forms practical — a linear flop+membw fit
    lands near the true rates via plain least squares, and the overlap
    model only has to refine them, instead of hoping a random multi-start
    finds a basin that spans six orders of magnitude in parameter scale.
    Order ``models`` from narrowest to broadest scope (the zoo's order).

    The table is densified once; each model's compiled solver comes from
    the signature-keyed solver cache, so a study re-run (or the same zoo
    fitted on the next machine) pays zero re-tracing.  ``nonneg`` maps
    model name → nonnegativity constraint, as :func:`fit_model` takes it
    (default True, the paper's cost-explanatory setting).
    """
    with spans.span("solve.fit"):
        table = as_feature_table(feature_table)
        nonneg = dict(nonneg or {})
        fits: Dict[str, FitResult] = {}
        ladder: Dict[str, float] = {}
        for name, model in models.items():
            p0 = {n: ladder[n] for n in model.param_names if n in ladder} \
                if warm_start and ladder else None
            with spans.span("solve.rung", model=name) as s:
                fit = fit_model(model, table, scale_by_output=scale_by_output,
                                nonneg=nonneg.get(name, True), seeds=seeds,
                                p0=p0, **solver_opts)
                s.attrs["iterations"] = fit.iterations
                s.attrs["converged"] = fit.converged
            fits[name] = fit
            # carry only positive estimates forward: a rate clamped to 0 by a
            # narrow model is a worse start (and a degenerate LM scale) than an
            # earlier model's coarse positive estimate
            ladder.update({k: v for k, v in fit.params.items() if v > 0})
        return fits


def relative_errors(model: Model, params: Mapping[str, float],
                    table: FeatureTableLike) -> Dict[str, float]:
    """Per-row |pred − meas| / meas of ``model`` under ``params`` against
    the table's measured output column — the cell values of the paper's
    per-variant accuracy tables (§8, Tables 3–6).

    Every feature the model reads must actually be a column of the table:
    a missing feature would silently evaluate as 0 and the resulting
    'accuracy' numbers would be fabrications, so it is an error instead
    (e.g. scoring a legacy fit against a study holdout that never
    gathered its features).
    """
    ft = as_feature_table(table)
    missing = [n for n in (model.output_feature, *model.feature_names)
               if n not in ft.feature_ids]
    if missing:
        raise ValueError(
            f"feature table lacks columns {missing} required by the "
            f"{model.output_feature!r} model; accuracy against it would "
            f"silently read them as 0 — re-gather with these features")
    meas = ft.column(model.output_feature)
    bad = np.flatnonzero(~(np.abs(meas) > 0))
    if bad.size:
        raise ValueError(
            f"measured output {model.output_feature!r} is zero for row "
            f"{ft.row_names[int(bad[0])]!r}; relative error is undefined")
    dt = _param_dtype()
    F = model.align(ft, missing="zero")     # presence validated above
    p_vec = jnp.asarray([params[n] for n in model.param_names], dt)
    pred = np.asarray(model.batched_eval(p_vec, jnp.asarray(F, dt)),
                      np.float64)
    rel = np.abs(pred - meas) / np.abs(meas)
    return {name: float(r) for name, r in zip(ft.row_names, rel)}


def _gmre(rel: Sequence[float]) -> float:
    """Geometric mean of relative errors, floored at 1e-12 (one place)."""
    clamped = [max(float(r), 1e-12) for r in rel]
    return float(np.exp(np.mean(np.log(clamped))))


def geometric_mean_relative_error(pred: Sequence[float],
                                  meas: Sequence[float]) -> float:
    """Paper's headline accuracy metric (Fleming & Wallace 1986)."""
    return _gmre([abs(p - m) / abs(m) for p, m in zip(pred, meas)])


def gmre_of(rel_errors: Mapping[str, float]) -> float:
    """Geometric-mean summary of a per-row relative-error map."""
    return _gmre(list(rel_errors.values()))
