"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel module contains the raw ``pl.pallas_call`` + BlockSpec tiling;
``ops.py`` holds the jit'd public wrappers (compiled Mosaic kernels;
``interpret=True`` runs them on a CPU host) and ``ref.py`` the pure-jnp
oracles every kernel is validated against.

Kernels:
  * ``matmul_tiled``    — blocked matmul (the paper's running example: the
    "prefetch into local memory" variant becomes VMEM tile staging)
  * ``flash_attention`` — streaming-softmax attention (causal / GQA /
    sliding window / logit softcap); removes the score-tile HBM round trips
    that dominate the jnp lowering's memory roofline term
  * ``mamba2_ssd``      — chunked SSD scan with VMEM-resident state
  * ``slstm_cell``      — the sLSTM time loop inside the kernel with the
    recurrent weights pinned in VMEM (removes the per-step HBM weight
    re-read that dominates the xlstm prefill roofline — §Perf H3)
  * ``stencil5``        — 2-D five-point stencil (paper §8.5 application)
  * ``dg_diff``         — batched small-matrix DG differentiation (§8.4)
  * ``stream`` / ``madd`` — UIPiCK measurement kernels (strided-memory and
    peak-FLOP microbenchmarks) as genuine TPU kernels
"""

import jax
import jax.numpy as jnp


def mxu_precision(dtype):
    """Dot precision for operands of ``dtype`` inside a kernel.

    Mosaic's default runs an f32 contraction as one bf16 MXU pass, so an
    f32 kernel would drift from its f32 reference; f32 operands get the
    full multi-pass ``HIGHEST``.  Narrower dtypes keep the native pass
    (Mosaic refuses ``HIGHEST`` for them)."""
    return jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32 \
        else None
