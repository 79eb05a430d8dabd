"""Pre-batching calibration engine, kept verbatim as a differential-testing
oracle.

This is the original row-by-row implementation: the residual evaluates the
model expression once per measurement row through a dict environment, the
LM loop re-traces the Jacobian every iteration, and each damping step
forces a host sync.  It is deliberately NOT fast — ``repro.core.calibrate``
is the production engine — but it is simple enough to be obviously correct,
so tests and ``benchmarks/calibration_bench.py`` use it to check that the
batched jit-compiled pipeline returns the same parameters (and to quantify
the speedup).
"""
from __future__ import annotations

from typing import Callable, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.model import Model, _param_dtype


def reference_residual_fn(model: Model,
                          feature_table: Sequence[Mapping[str, float]],
                          *, scale_by_output: bool = True):
    """Row-wise residual builder (the original ``Model.residual_fn``)."""
    rows = []
    for i, row in enumerate(feature_table):
        t = float(row[model.output_feature])
        feats = {n: float(row.get(n, 0.0)) for n in model.feature_names}
        if scale_by_output:
            if not t > 0:
                raise ValueError(
                    f"output feature {model.output_feature!r} must be "
                    f"positive to scale; row {i} has value {t!r}")
            feats = {k: v / t for k, v in feats.items()}
            rows.append((feats, 1.0))
        else:
            rows.append((feats, t))

    pn = model.param_names

    def resid(p_vec: jax.Array) -> jax.Array:
        outs = []
        for feats, t in rows:
            env = {n: p_vec[i] for i, n in enumerate(pn)}
            env.update({k: jnp.asarray(v) for k, v in feats.items()})
            outs.append(t - model._eval(env))
        return jnp.stack(outs)

    p0 = jnp.full((len(pn),), 1e-9, _param_dtype())
    return resid, p0, pn


def reference_levenberg_marquardt(
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
    """Python-loop LM with per-iteration host syncs (the original)."""
    jac = jax.jacobian(resid_fn)
    p = jnp.asarray(p0, _param_dtype())
    lam = lam0
    r = resid_fn(p)
    cost = float(jnp.sum(r * r))
    it = 0
    converged = False
    for it in range(1, max_iters + 1):
        J = jac(p)
        JTJ = jnp.matmul(J.T, J, precision=jax.lax.Precision.HIGHEST)
        JTr = jnp.matmul(J.T, r, precision=jax.lax.Precision.HIGHEST)
        stepped = False
        for _ in range(20):  # inner damping search
            A = JTJ + lam * jnp.diag(jnp.maximum(jnp.diag(JTJ), 1e-20))
            dp = jnp.linalg.solve(A, -JTr)
            if not bool(jnp.isfinite(dp).all()):  # singular — bump damping
                lam *= lam_up
                continue
            p_new = p + dp
            if nonneg:
                p_new = jnp.maximum(p_new, 0.0)
            r_new = resid_fn(p_new)
            cost_new = float(jnp.sum(r_new * r_new))
            if np.isfinite(cost_new) and cost_new < cost:
                rel = (cost - cost_new) / max(cost, 1e-30)
                p, r, cost = p_new, r_new, cost_new
                lam = max(lam * lam_down, 1e-12)
                stepped = True
                if rel < tol:
                    converged = True
                break
            lam *= lam_up
        if not stepped or converged:
            converged = converged or not stepped
            break
    return p, float(np.sqrt(cost)), it, converged


def reference_fit_model(
    model: Model,
    feature_table: Sequence[Mapping[str, float]],
    *,
    scale_by_output: bool = True,
    p0: Optional[Mapping[str, float]] = None,
    nonneg: bool = False,
    seeds: int = 3,
    max_iters: int = 200,
):
    """Sequential multi-start fit (original ``fit_model``); returns the
    ``(params dict, residual_norm)`` of the best start."""
    resid, p_init, names = reference_residual_fn(
        model, feature_table, scale_by_output=scale_by_output)
    if p0:
        p_init = jnp.asarray([p0.get(n, 1e-9) for n in names])

    starts = [p_init]
    key = jax.random.PRNGKey(0)
    for _ in range(seeds - 1):
        key, sub = jax.random.split(key)
        starts.append(p_init * jnp.exp(
            jax.random.uniform(sub, p_init.shape, minval=-2.0, maxval=2.0)))
    starts = [s.at[jnp.asarray(
        [i for i, n in enumerate(names) if "edge" in n], jnp.int32)].set(100.0)
        if any("edge" in n for n in names) else s for s in starts]

    best = None
    for s in starts:
        p, rn, it, conv = reference_levenberg_marquardt(
            resid, s, nonneg=nonneg, max_iters=max_iters)
        if best is None or rn < best[1]:
            best = (p, rn)
    p, rn = best
    return {n: float(v) for n, v in zip(names, p)}, rn
