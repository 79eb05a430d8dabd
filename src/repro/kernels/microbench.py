"""UIPiCK measurement kernels as genuine Pallas TPU kernels.

``stream_strided`` — the paper's parameterized global-memory access-pattern
microbenchmark: the *block-stride* argument is the TPU analogue of the
paper's group-ID stride (which block of HBM each grid step touches), and
dtype/width map directly.  XLA lays a 1-D array out on the TPU in tiles of
1024 elements, and Mosaic refuses a 1-D block whose tiling differs, so
1-D blocks here are multiples of :data:`BLOCK_ALIGN`.

``madd_throughput`` — the paper's peak-FLOP kernel (SHOC MaxFlops pattern):
a VMEM-resident block is updated by an ``iters``-deep fused multiply-add
chain with 8 independent streams, so the MXU/VPU pipeline stays full and
HBM traffic is negligible.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: element granularity of a 1-D block that matches XLA's TPU layout
BLOCK_ALIGN = 1024


def _stream_kernel(*refs):
    o_ref = refs[-1]
    acc = refs[0][...].astype(jnp.float32)
    for r in refs[1:-1]:
        acc = acc + r[...].astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def stream_strided(
    arrays,                # list of [S] inputs, S = n_blocks·stride·block
    *,
    block: int = BLOCK_ALIGN,
    stride: int = 1,       # block-stride: which HBM blocks each step reads
    interpret: bool = False,
) -> jax.Array:
    assert block % BLOCK_ALIGN == 0, (block, BLOCK_ALIGN)
    (S,) = arrays[0].shape
    n_out = S // (block * stride)
    assert n_out * block * stride == S

    in_specs = [pl.BlockSpec((block,), lambda i, s=stride: (i * s,))
                for _ in arrays]
    return pl.pallas_call(
        _stream_kernel,
        grid=(n_out,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_out * block,), arrays[0].dtype),
        interpret=interpret,
    )(*arrays)


def _madd_kernel(x_ref, o_ref, *, iters: int, a: float, b: float):
    dt = x_ref.dtype
    xs = [x_ref[...] + jnp.asarray(i, dt) for i in range(8)]

    def body(_, xs):
        return [xi * jnp.asarray(a, dt) + jnp.asarray(b, dt) for xi in xs]

    xs = jax.lax.fori_loop(0, iters, body, xs)
    out = xs[0]
    for xi in xs[1:]:
        out = out + xi
    o_ref[...] = out


def madd_throughput(
    x: jax.Array,          # [S]
    *,
    iters: int = 256,
    block: int = 2048,
    a: float = 1.000001,
    b: float = 1e-7,
    interpret: bool = False,
) -> jax.Array:
    (S,) = x.shape
    blk = min(block, S)
    assert S % blk == 0 and blk % BLOCK_ALIGN == 0, (S, blk, BLOCK_ALIGN)
    return pl.pallas_call(
        functools.partial(_madd_kernel, iters=iters, a=a, b=b),
        grid=(S // blk,),
        in_specs=[pl.BlockSpec((blk,), lambda i: (i,))],
        out_specs=pl.BlockSpec((blk,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((S,), x.dtype),
        interpret=interpret,
    )(x)
