"""AdamW with cosine schedule, global-norm clipping, and dtype-configurable
moments (bf16 moments for the 480B/236B archs so optimizer state fits HBM).

Optimizer state is a plain pytree mirroring the parameter tree, so the same
logical-axis sharding rules apply verbatim (FSDP-sharded optimizer state —
ZeRO-style — falls out of the rules table for free).
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import OptimizerConfig


class OptState(NamedTuple):
    mu: Any      # first moment  (param-tree shaped)
    nu: Any      # second moment (param-tree shaped)
    count: jax.Array  # scalar int32 step


def init_opt_state(params: Any, ocfg: OptimizerConfig) -> OptState:
    mdt = jnp.dtype(ocfg.moment_dtype)
    # zeros_like keeps each parameter's sharding: the moments are born
    # split across the mesh as the parameters are, not gathered on one chip
    zeros = lambda p: jnp.zeros_like(p, dtype=mdt)
    return OptState(
        mu=jax.tree.map(zeros, params),
        nu=jax.tree.map(zeros, params),
        count=jnp.zeros((), jnp.int32),
    )


def abstract_opt_state(abstract_params: Any, ocfg: OptimizerConfig) -> OptState:
    mdt = jnp.dtype(ocfg.moment_dtype)
    sds = lambda p: jax.ShapeDtypeStruct(p.shape, mdt)
    return OptState(
        mu=jax.tree.map(sds, abstract_params),
        nu=jax.tree.map(sds, abstract_params),
        count=jax.ShapeDtypeStruct((), jnp.int32),
    )


def opt_state_axes(param_axes: Any) -> OptState:
    """Logical-axis tree for the optimizer state (mirrors parameters)."""
    return OptState(mu=param_axes, nu=param_axes, count=())


def lr_schedule(ocfg: OptimizerConfig, step: jax.Array) -> jax.Array:
    """Linear warmup → cosine decay to 10% of peak."""
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / max(ocfg.warmup_steps, 1), 1.0)
    prog = jnp.clip(
        (step - ocfg.warmup_steps)
        / max(ocfg.total_steps - ocfg.warmup_steps, 1),
        0.0, 1.0,
    )
    cos = 0.5 * (1.0 + jnp.cos(math.pi * prog))
    return ocfg.learning_rate * warm * (0.1 + 0.9 * cos)


def global_norm(tree: Any) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def _quantize_grads(grads: Any, mode: str) -> Any:
    """Gradient compression hook applied before the optimizer update.

    "bf16": cast (the default wire format already — documents intent)
    "int8": symmetric per-tensor int8 quantize/dequantize (lossy).
    """
    if mode == "none":
        return grads
    if mode == "bf16":
        return jax.tree.map(lambda g: g.astype(jnp.bfloat16), grads)
    if mode == "int8":
        def q(g):
            gf = g.astype(jnp.float32)
            scale = jnp.maximum(jnp.max(jnp.abs(gf)), 1e-12) / 127.0
            qi = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int8)
            return qi.astype(jnp.float32) * scale
        return jax.tree.map(q, grads)
    raise ValueError(mode)


def apply_updates(
    params: Any,
    grads: Any,
    state: OptState,
    ocfg: OptimizerConfig,
) -> Tuple[Any, OptState, Dict[str, jax.Array]]:
    grads = _quantize_grads(grads, ocfg.grad_compression)
    gnorm = global_norm(grads)
    clip = jnp.minimum(1.0, ocfg.grad_clip_norm / jnp.maximum(gnorm, 1e-12))
    count = state.count + 1
    cf = count.astype(jnp.float32)
    lr = lr_schedule(ocfg, count)
    bc1 = 1.0 - ocfg.b1 ** cf
    bc2 = 1.0 - ocfg.b2 ** cf
    mdt = jnp.dtype(ocfg.moment_dtype)

    def upd(p, g, m, v):
        gf = g.astype(jnp.float32) * clip
        m_new = ocfg.b1 * m.astype(jnp.float32) + (1 - ocfg.b1) * gf
        v_new = ocfg.b2 * v.astype(jnp.float32) + (1 - ocfg.b2) * gf * gf
        mhat = m_new / bc1
        vhat = v_new / bc2
        step_ = mhat / (jnp.sqrt(vhat) + ocfg.eps)
        if p.ndim >= 2:  # decoupled weight decay on matrices only
            step_ = step_ + ocfg.weight_decay * p.astype(jnp.float32)
        p_new = p.astype(jnp.float32) - lr * step_
        return p_new.astype(p.dtype), m_new.astype(mdt), v_new.astype(mdt)

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state.mu)
    flat_v = treedef.flatten_up_to(state.nu)
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_p, OptState(new_m, new_v, count), metrics
