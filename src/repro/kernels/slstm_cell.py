"""sLSTM recurrent cell as a Pallas TPU kernel (§Perf H3 follow-through).

The xlstm-125m prefill roofline is dominated by the per-timestep recurrent
matmul re-reading ``r_gates`` (2.4 MB) from HBM 32768 times per layer.
This kernel runs the time loop *inside* the kernel with the recurrent
weights pinned in VMEM: HBM traffic drops to one streaming read of the
precomputed input-gate contributions ``g_in`` and one write of the hidden
trajectory — the roofline lower bound for a sequential recurrence.

Stabilized exponential gating (running per-cell max ``m``), identical math
to ``repro.models.xlstm._slstm_cell``.

Layout: the wrapper makes the operands time-major with the batch on
sublanes (``g_in`` → ``[S, 4, H, B, dh]``), so each (gate, head) slice the
loop reads is a ``[B, dh]`` tile and each recurrent product is one
``[B, dh] @ [dh, dh]`` MXU matmul — no in-kernel reshape or transpose.
Grid: one program per ``SEQ_TILE`` time steps, sequential ("arbitrary"),
with the (c, n, m, h) state carried across programs in VMEM scratch; the
sequence tile keeps the streamed blocks inside the scoped VMEM budget at
published widths (xlstm-125m: 4 heads × 192, seq 2048).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mxu_precision


_F32 = mxu_precision(jnp.float32)
SEQ_TILE = 16       # time steps per grid program


def _slstm_kernel(g_in_ref, r_ref, b_ref, y_ref, c_ref, n_ref, m_ref, h_ref,
                  *, H: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.zeros_like(m_ref)
        h_ref[...] = jnp.zeros_like(h_ref)

    def step(t, _):
        for hd in range(H):
            h = h_ref[hd]                                   # [B, dh]
            # per head and gate: input contribution + h · r + bias
            li, lf, z_raw, o_raw = (
                g_in_ref[t, g, hd].astype(jnp.float32)
                + jnp.dot(h, r_ref[hd, g].astype(jnp.float32),
                          precision=_F32,
                          preferred_element_type=jnp.float32)
                + b_ref[g, hd].astype(jnp.float32)
                for g in range(4))
            lf = jax.nn.log_sigmoid(lf)
            m_prev = m_ref[hd]
            m_new = jnp.maximum(lf + m_prev, li)
            ip = jnp.exp(li - m_new)
            fp = jnp.exp(lf + m_prev - m_new)
            c_new = fp * c_ref[hd] + ip * jnp.tanh(z_raw)
            n_new = fp * n_ref[hd] + ip
            h_new = jax.nn.sigmoid(o_raw) * c_new / jnp.maximum(n_new, 1e-6)
            c_ref[hd] = c_new
            n_ref[hd] = n_new
            m_ref[hd] = m_new
            h_ref[hd] = h_new
            y_ref[t, hd] = h_new.astype(y_ref.dtype)
        return ()

    jax.lax.fori_loop(0, SEQ_TILE, step, ())


def slstm_cell(
    g_in: jax.Array,    # [B, S, 4, H, dh] — input contributions (x · W)
    r_gates: jax.Array,  # [H, dh, 4, dh]
    b_gates: jax.Array,  # [4, H, dh]
    *,
    interpret: bool = False,
) -> jax.Array:
    """Returns the hidden trajectory h: [B, S, H, dh].  ``S`` must be a
    multiple of ``SEQ_TILE``."""
    B, S, four, H, dh = g_in.shape
    assert four == 4
    if S % SEQ_TILE:
        raise ValueError(f"slstm_cell: sequence length {S} is not a multiple "
                         f"of the {SEQ_TILE}-step tile")
    g_t = g_in.transpose(1, 2, 3, 0, 4)                 # [S, 4, H, B, dh]
    r_t = r_gates.transpose(0, 2, 1, 3)                 # [H, 4, dh, dh]
    b_t = b_gates[:, :, None, :]                        # [4, H, 1, dh]

    kernel = functools.partial(_slstm_kernel, H=H)
    y = pl.pallas_call(
        kernel,
        grid=(S // SEQ_TILE,),
        in_specs=[
            pl.BlockSpec((SEQ_TILE, 4, H, B, dh), lambda s: (s, 0, 0, 0, 0)),
            pl.BlockSpec((H, 4, dh, dh), lambda s: (0, 0, 0, 0)),
            pl.BlockSpec((4, H, 1, dh), lambda s: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((SEQ_TILE, H, B, dh), lambda s: (s, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((S, H, B, dh), g_in.dtype),
        scratch_shapes=[pltpu.VMEM((H, B, dh), jnp.float32)] * 4,  # c n m h
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(g_t, r_t, b_t)
    return y.transpose(2, 0, 1, 3)
