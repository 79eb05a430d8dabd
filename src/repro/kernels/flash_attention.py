"""Flash attention (streaming softmax) Pallas TPU kernel.

Covers every attention flavour the assigned archs need: causal, GQA
(Hq = G·Hkv), sliding window (gemma-2 local layers) and logit soft-capping.

Tiling: grid = (batch, q_heads, Sq/bq, Skv/bk); the kv axis is the fastest,
sequential ("arbitrary") dimension so the running max / denominator /
accumulator scratch persists across it in VMEM.  Score tiles (bq × bk)
never touch HBM — this is precisely the traffic the roofline analysis
attributes ~1/3 of the jnp lowering's memory term to.

Causal masked-out tiles are still *visited* (block-level skipping via
dynamic grids is a further optimization recorded in EXPERIMENTS §Perf);
the mask zeroes them numerically.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mxu_precision

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  softcap: Optional[float], block_q: int, block_k: int,
                  n_k: int):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]  # [bq, D]
    k = k_ref[...]  # [bk, D]
    v = v_ref[...]  # [bk, Dv]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), precision=mxu_precision(q.dtype),
        preferred_element_type=jnp.float32) * scale      # [bq, bk]
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    iq = pl.program_id(2)
    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)  # fully-masked tile guard
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        precision=mxu_precision(v.dtype),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == n_k - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """q: [B, Sq, Hq, D]; k, v: [B, Skv, Hkv, D*] → [B, Sq, Hq, Dv]."""
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, Dv = v.shape
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    bq, bk = min(block_q, Sq), min(block_k, Skv)
    assert Sq % bq == 0 and Skv % bk == 0
    n_k = Skv // bk

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=bq, block_k=bk, n_k=n_k)

    # heads before sequence: Mosaic tiles the last two block dims, so the
    # (bq, D) tile must be the trailing pair — a (bq, 1, D) slice of the
    # [B, S, H, D] layout is refused
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, Sq // bq, n_k),
        in_specs=[
            pl.BlockSpec((None, None, bq, D),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((None, None, bk, D),
                         lambda b, h, iq, ik, G=G: (b, h // G, ik, 0)),
            pl.BlockSpec((None, None, bk, Dv),
                         lambda b, h, iq, ik, G=G: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, Dv),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
