"""2-D five-point stencil Pallas kernel (paper §8.5 application).

The paper's two OpenCL variants differ in work-group/tile size (16×16 vs
18×18 with halo threads idling).  On TPU the analogous knob is the VMEM
block shape.  Each grid step needs a (bm+2)×(bn+2) halo window; the
wrapper pads the grid so that a tile-aligned (bm+8)×(bn+128) window
starting at (i·bm, j·bn) covers it, and an element-indexed BlockSpec lets
the Pallas pipeline DMA these overlapping windows HBM → VMEM.  Halo
*reads* therefore overlap between neighbouring blocks (the AFR > 1 access
the paper models), but every output element is written once.  The four
neighbour taps are lane/sublane rotations (``pltpu.roll``) of the window,
so every slice the kernel takes starts at (0, 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# window over-fetch that keeps every block tile-aligned: one (8, 128) f32
# tile of halo rows/columns instead of the exact 2
_HALO_ROWS, _HALO_COLS = 8, 128


def _stencil_kernel(u_ref, o_ref, *, bm: int, bn: int):
    win = u_ref[...]                       # [bm + 8, bn + 128]
    rows, cols = win.shape

    def tap(dr: int, dc: int):
        # element (r, c) of the result is win[r + dr, c + dc]
        t = win
        if dr:
            t = pltpu.roll(t, rows - dr, 0)
        if dc:
            t = pltpu.roll(t, cols - dc, 1)
        return t[:bm, :bn]

    out = tap(0, 1) + tap(2, 1) + tap(1, 0) + tap(1, 2) - 4.0 * tap(1, 1)
    o_ref[...] = out.astype(o_ref.dtype)


def stencil5(
    u: jax.Array,          # [M, N] — interior; result has the same shape
    *,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
) -> jax.Array:
    M, N = u.shape
    bm, bn = min(block_m, M), min(block_n, N)
    assert M % bm == 0 and N % bn == 0
    # one zero ring of boundary, then enough extra zeros that the last
    # aligned window stays in bounds
    up = jnp.pad(u, ((1, _HALO_ROWS - 1), (1, _HALO_COLS - 1)))

    kernel = functools.partial(_stencil_kernel, bm=bm, bn=bn)
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn),
        in_specs=[pl.BlockSpec(
            (pl.Element(bm + _HALO_ROWS), pl.Element(bn + _HALO_COLS)),
            lambda i, j: (i * bm, j * bn))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), u.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(up)
