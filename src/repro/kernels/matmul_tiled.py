"""Blocked matrix multiplication — the paper's running example, TPU-native.

The paper's tiled-with-prefetch OpenCL matmul stages 16×16 tiles of A and B
in local (shared) memory.  The TPU translation: BlockSpecs stage
(bm × bk) / (bk × bn) tiles in VMEM, and the MXU consumes them directly —
"prefetching" is what the Pallas pipeline does between grid steps.  Block
shapes must be multiples of (8, 128) lanes for the MXU; the k grid axis is
sequential ("arbitrary") so the f32 VMEM accumulator persists across it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mxu_precision


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], precision=mxu_precision(a_ref.dtype),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_tiled(
    a: jax.Array,
    b: jax.Array,
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """a: [M, K] @ b: [K, N] → [M, N] with VMEM tile staging."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    n_k = K // bk

    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=n_k),
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
