"""Public jit'd wrappers around the Pallas kernels.

Every wrapper compiles the real Mosaic kernel unless the caller passes
``interpret=True``: the interpreter runs the kernel body in Python with the
exact TPU semantics, which is how the per-kernel allclose tests validate
against ``ref.py`` on a CPU host.  Executing a wrapper on a CPU backend
without ``interpret=True`` fails; tracing one (``jax.make_jaxpr``, static
pricing) works anywhere.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax

from repro.kernels import dg_diff as _dg
from repro.kernels import flash_attention as _fa
from repro.kernels import mamba2_ssd as _ssd
from repro.kernels import matmul_tiled as _mm
from repro.kernels import microbench as _mb
from repro.kernels import slstm_cell as _sc
from repro.kernels import stencil5 as _st


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_n", "block_k", "interpret"))
def matmul(a, b, *, block_m: int = 256, block_n: int = 256,
           block_k: int = 256, interpret: bool = False):
    return _mm.matmul_tiled(a, b, block_m=block_m, block_n=block_n,
                            block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "block_q", "block_k",
    "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba2_ssd(xdt, da, Bm, Cm, *, chunk: int = 256,
               interpret: bool = False):
    return _ssd.mamba2_ssd(xdt, da, Bm, Cm, chunk=chunk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_n", "interpret"))
def stencil5(u, *, block_m: int = 256, block_n: int = 256,
             interpret: bool = False):
    return _st.stencil5(u, block_m=block_m, block_n=block_n,
                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_e", "interpret"))
def dg_diff(diff_mat, ut, *, block_e: int = 512, interpret: bool = False):
    return _dg.dg_diff(diff_mat, ut, block_e=block_e, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block", "stride", "interpret"))
def stream_strided(arrays: Sequence[jax.Array], *,
                   block: int = _mb.BLOCK_ALIGN, stride: int = 1,
                   interpret: bool = False):
    return _mb.stream_strided(list(arrays), block=block, stride=stride,
                              interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "iters", "block", "a", "b", "interpret"))
def madd_throughput(x, *, iters: int = 256, block: int = 2048,
                    a: float = 1.000001, b: float = 1e-7,
                    interpret: bool = False):
    return _mb.madd_throughput(x, iters=iters, block=block, a=a, b=b,
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def slstm_cell(g_in, r_gates, b_gates, *, interpret: bool = False):
    return _sc.slstm_cell(g_in, r_gates, b_gates, interpret=interpret)
