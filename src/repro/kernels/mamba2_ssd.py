"""Mamba-2 SSD chunked scan as a Pallas TPU kernel.

Grid = (batch, heads, chunks); the chunk axis is sequential so the
inter-chunk state ``[P, N]`` lives in VMEM scratch for the whole sequence —
the kernel's HBM traffic is one read of (x, B, C, the log-decays) and one
write of y per token, which is the roofline lower bound for this op.

Mosaic tiles the last two dims of every block, so the wrapper lays the
operands out heads-first (``[B, H, S, *]``): each block is then a
``(chunk, P)`` / ``(chunk, N)`` tile.  The chunk-local cumulative log-decay
``la`` is a cheap XLA cumsum in the wrapper and enters the kernel twice,
as a column ``(chunk, 1)`` and as a row ``(1, chunk)``, so the decay
matrix ``exp(la_i − la_j)`` is a broadcast difference with no in-kernel
transpose.

Within a chunk (length L): the intra-chunk contribution is the
decay-masked quadratic form from the SSD paper; the inter-chunk part
applies the carried state.  All arithmetic in f32 on the MXU/VPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mxu_precision


def _dot(a, b, contract):
    """f32 contraction over dims ``contract`` at full MXU precision."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=mxu_precision(jnp.float32),
                               preferred_element_type=jnp.float32)


def _ssd_kernel(xdt_ref, lac_ref, lar_ref, b_ref, c_ref, y_ref, state_ref, *,
                chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = xdt_ref[...].astype(jnp.float32)          # [L, P]
    la_c = lac_ref[...]                           # [L, 1]
    la_r = lar_ref[...]                           # [1, L]
    Bm = b_ref[...].astype(jnp.float32)           # [L, N]
    Cm = c_ref[...].astype(jnp.float32)           # [L, N]

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(ii >= jj, jnp.exp(la_c - la_r), 0.0)  # [L, L]

    cb = _dot(Cm, Bm, ((1,), (1,)))                         # [L, L]
    y_intra = _dot(cb * decay, x, ((1,), (0,)))
    y_inter = _dot(Cm, state_ref[...], ((1,), (1,))) * jnp.exp(la_c)

    # state' = exp(la_L)·state + Σ_j exp(la_L − la_j)·B_j ⊗ x_j
    la_last = la_c[chunk - 1:, :]                 # [1, 1]
    w = jnp.exp(la_last - la_c)                   # [L, 1]
    ds = _dot(x * w, Bm, ((0,), (0,)))                      # [P, N]
    # a [1, 1] → [P, N] broadcast crosses sublanes and lanes at once, which
    # Mosaic refuses; going through a [1, N] row keeps each step legal
    carry = jnp.exp(jnp.broadcast_to(la_last, (1, state_ref.shape[1])))
    state_ref[...] = state_ref[...] * carry + ds

    y_ref[...] = (y_intra + y_inter).astype(y_ref.dtype)


def mamba2_ssd(
    xdt: jax.Array,   # [B, S, H, P]  (inputs pre-scaled by dt)
    da: jax.Array,    # [B, S, H]     (dt · A, negative log-decays)
    Bm: jax.Array,    # [B, S, H, N]  (per-head B, groups pre-broadcast)
    Cm: jax.Array,    # [B, S, H, N]
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    B, S, H, P = xdt.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk

    # chunk-local cumulative log-decay, heads-first: [B, H, nc, chunk]
    la = jnp.cumsum(da.astype(jnp.float32).transpose(0, 2, 1)
                    .reshape(B, H, nc, chunk), axis=-1)
    xt, bt, ct = (a.transpose(0, 2, 1, 3) for a in (xdt, Bm, Cm))

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((None, None, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, None, chunk, 1),
                         lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((None, None, None, 1, chunk),
                         lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((None, None, chunk, N), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, chunk, N), lambda b, h, c: (b, h, c, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, chunk, P),
                               lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, P), xdt.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xt, la[..., None], la[..., None, :], bt, ct)
    return y.transpose(0, 2, 1, 3)
