"""DG element-wise differentiation Pallas kernel (paper §8.4).

res[m, e, i] = Σ_j diff_mat[m, i, j] · u[e, j] — a batch of small (N×N)
matrices applied to a wide element matrix.  The paper's fastest variant
transposes the element data so loads are unit-stride; the TPU translation
keeps the element axis on lanes (last dim, 128-aligned blocks) and the
small diff_mat resident in VMEM across the whole element sweep.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mxu_precision


def _dg_kernel(d_ref, ut_ref, o_ref):
    d = d_ref[0]            # [N, N]
    ut = ut_ref[...]        # [N, be]  (transposed element data)
    o_ref[0] = jnp.dot(d, ut, precision=mxu_precision(d.dtype),
                       preferred_element_type=jnp.float32).astype(
        o_ref.dtype)


def dg_diff(
    diff_mat: jax.Array,   # [M, N, N]
    ut: jax.Array,         # [N, K]  — element data, transposed layout
    *,
    block_e: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Returns [M, N, K]."""
    M, N, _ = diff_mat.shape
    _, K = ut.shape
    be = min(block_e, K)
    assert K % be == 0

    return pl.pallas_call(
        _dg_kernel,
        grid=(M, K // be),
        in_specs=[
            pl.BlockSpec((1, N, N), lambda m, e: (m, 0, 0)),
            pl.BlockSpec((N, be), lambda m, e: (0, e)),
        ],
        out_specs=pl.BlockSpec((1, N, be), lambda m, e: (m, 0, e)),
        out_shape=jax.ShapeDtypeStruct((M, N, K), ut.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(diff_mat, ut)
