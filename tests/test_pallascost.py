"""Static Pallas cost analyzer: grid-scaled counts and block-spec HBM
traffic match closed-form ground truth — with zero kernel executions.

``pallas_call`` is no longer opaque: :mod:`repro.analysis.pallascost`
walks the kernel-body jaxpr abstractly, scales per-program counts by the
grid size, and derives HBM↔VMEM traffic from each operand's BlockSpec
(block shape × index-map refetch pattern × grid extent).  These tests pin
the derived features against hand-computed formulas for five wrappers —
matmul, stencil5, flash_attention, mamba2_ssd, slstm_cell — at ≥ 2
shapes each, entirely from ``ShapeDtypeStruct`` arguments (no device arrays
exist to execute), with kernel timing POISONED for good measure.

A deliberately non-affine fixture (index map ``i * i``) pins the failure
mode: the counter stays silent (no fabricated features) and the scope
auditor reports the precise ``pallas-unanalyzable`` diagnostic instead of
a blanket opacity error.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from repro.analysis import PallasUnanalyzable, audit_callable
from repro.analysis.pallascost import (
    BYTES_IN_FEATURE,
    BYTES_OUT_FEATURE,
    unanalyzable_reason,
)
from repro.api import PerfSession
from repro.core.calibrate import FitResult
from repro.core.counting import count_fn
from repro.core.model import Model
from repro.core.uipick import CountingTimer, MeasurementKernel
from repro.kernels import ops
from repro.kernels.slstm_cell import SEQ_TILE
from repro.profiles import DeviceFingerprint, MachineProfile, ModelFit


def _profile() -> MachineProfile:
    """A tiny in-memory profile whose overlap model prices the madd and
    contiguous-memory features the analyzer derives (no file, no device)."""
    model = Model(
        "f_wall_time_cpu_host",
        "overlap2(p_madd * f_op_float32_madd, "
        "p_mem * (f_mem_contig_float32_load "
        "+ f_mem_contig_float32_store + f_op_float32_add), p_edge) "
        "+ p_launch * f_sync_launch_kernel")
    fit = FitResult(params={"p_madd": 5e-11, "p_mem": 4e-10,
                            "p_launch": 3e-6, "p_edge": 40.0},
                    residual_norm=0.0, iterations=1, converged=True)
    return MachineProfile(
        fingerprint=DeviceFingerprint(platform="synth",
                                      device_kind="pallas-test",
                                      n_devices=1),
        fits={"ovl_flop_mem": ModelFit.from_fit(model, fit)},
        trials=4)


@pytest.fixture(autouse=True)
def no_execution(monkeypatch):
    def boom(self, *a, **k):
        raise AssertionError(
            "static pallas analysis must never execute a kernel")

    monkeypatch.setattr(MeasurementKernel, "time", boom)
    monkeypatch.setattr(MeasurementKernel, "time_stats", boom)
    monkeypatch.setattr(MeasurementKernel, "jitted", boom)


def _f32(*shape) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, jnp.float32)


# ---------------------------------------------------------------------------
# ground truth: grid-scaled body counts and block-spec byte traffic
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("M,N,K,b", [
    (256, 384, 512, 128),
    (128, 128, 128, 128),
    (512, 256, 128, 64),
])
def test_matmul_counts_match_closed_form(M, N, K, b):
    fn = functools.partial(ops.matmul, block_m=b, block_n=b, block_k=b)
    c = count_fn(fn, _f32(M, K), _f32(K, N))
    gm, gn, gk = M // b, N // b, K // b
    # every (m, n, k) grid program multiplies one b×b×b tile pair
    assert c["f_op_float32_madd"] == M * N * K
    # A and B each refetch a b×b block at every grid step (k varies
    # fastest → the A block changes whenever k does, B always)
    assert c[BYTES_IN_FEATURE] == 4 * gm * gn * gk * (b * b + b * b)
    # the output block is written once per (m, n) tile
    assert c[BYTES_OUT_FEATURE] == 4 * M * N
    # block traffic is also priced in elements for the stock memory term
    assert c["f_mem_contig_float32_load"] == 2 * gm * gn * gk * b * b
    assert c["f_sync_grid_programs"] == gm * gn * gk


@pytest.mark.parametrize("M,N,bm,bn", [
    (256, 512, 128, 128),
    (256, 256, 128, 128),
    (512, 512, 256, 128),
])
def test_stencil5_counts_match_closed_form(M, N, bm, bn):
    fn = functools.partial(ops.stencil5, block_m=bm, block_n=bn)
    c = count_fn(fn, _f32(M, N))
    gm, gn = M // bm, N // bn
    # haloed input window per grid program: the (bm+2)×(bn+2) halo widened
    # to one (8, 128) tile of over-fetch so every DMA stays tile aligned
    assert c[BYTES_IN_FEATURE] == 4 * gm * gn * (bm + 8) * (bn + 128)
    assert c[BYTES_OUT_FEATURE] == 4 * M * N
    # 5-point stencil: 4 adds + 1 scale per output element
    assert c["f_op_float32_add"] == 4 * M * N
    assert c["f_op_float32_mul"] == M * N


@pytest.mark.parametrize("B,S,Hq,Hkv,D,bq,bk", [
    (2, 256, 8, 2, 64, 64, 64),
    (1, 128, 4, 4, 64, 64, 64),
    (2, 512, 8, 2, 64, 128, 64),
])
def test_flash_attention_counts_match_closed_form(B, S, Hq, Hkv, D, bq, bk):
    fn = functools.partial(ops.flash_attention, causal=True,
                           block_q=bq, block_k=bk)
    c = count_fn(fn, _f32(B, S, Hq, D), _f32(B, S, Hkv, D),
                 _f32(B, S, Hkv, D))
    nq, nk = S // bq, S // bk
    # QK^T (S·S·D) plus PV (S·S·D) per (batch, q-head)
    assert c["f_op_float32_madd"] == B * Hq * S * S * (D + D)
    # Q fetched once per q-block; K and V refetched for every (q, k) pair
    # — the GQA head map (floor-div index map) changes nothing per-block
    q_bytes = 4 * B * Hq * nq * bq * D
    k_bytes = 4 * B * Hq * nq * nk * bk * D
    v_bytes = 4 * B * Hq * nq * nk * bk * D
    assert c[BYTES_IN_FEATURE] == q_bytes + k_bytes + v_bytes
    assert c[BYTES_OUT_FEATURE] == 4 * B * Hq * S * D
    # exp over every bq×bk score tile + one per-row rescale exp
    assert c["f_op_float32_transc"] == B * Hq * nq * nk * (bq * bk + bq)


@pytest.mark.parametrize("B,S,H,P,N,L", [
    (2, 128, 4, 32, 16, 32),
    (1, 256, 2, 64, 64, 64),
])
def test_mamba2_ssd_counts_match_closed_form(B, S, H, P, N, L):
    fn = functools.partial(ops.mamba2_ssd, chunk=L)
    c = count_fn(fn, _f32(B, S, H, P), _f32(B, S, H), _f32(B, S, H, N),
                 _f32(B, S, H, N))
    programs = B * H * (S // L)
    # per token: x (P), B and C (N each), and the chunk-local log-decay
    # twice — as the column and as the row of the decay matrix
    assert c[BYTES_IN_FEATURE] == 4 * B * H * S * (P + 2 + 2 * N)
    assert c[BYTES_OUT_FEATURE] == 4 * B * H * S * P
    # per chunk: C·Bᵀ and its product with x (L² each), the inter-chunk
    # read-out and the state update (L·N·P each)
    assert c["f_op_float32_madd"] == programs * (
        L * L * (N + P) + 2 * L * N * P)
    assert c["f_sync_grid_programs"] == programs


@pytest.mark.parametrize("B,S,H,dh", [
    (2, 32, 4, 16),
    (8, 64, 2, 32),
])
def test_slstm_cell_counts_match_closed_form(B, S, H, dh):
    c = count_fn(ops.slstm_cell, _f32(B, S, 4, H, dh), _f32(H, dh, 4, dh),
                 _f32(4, H, dh))
    # g_in streams once; the recurrent weights and biases keep one block
    # index for the whole grid, so they are fetched once and stay in VMEM
    assert c[BYTES_IN_FEATURE] == 4 * (S * 4 * H * B * dh
                                       + H * 4 * dh * dh + 4 * H * dh)
    assert c[BYTES_OUT_FEATURE] == 4 * S * H * B * dh
    # per step, head and gate: one [B, dh] @ [dh, dh] recurrent product
    assert c["f_op_float32_madd"] == S * H * 4 * B * dh * dh
    assert c["f_sync_grid_programs"] == S // SEQ_TILE


# ---------------------------------------------------------------------------
# the unanalyzable path: precise diagnostic, silent counter
# ---------------------------------------------------------------------------


def _copy_body(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _nonaffine(x):
    # index map multiplies two grid-dependent values: no affine footprint
    return pl.pallas_call(
        _copy_body,
        grid=(4,),
        in_specs=[pl.BlockSpec((16, 64), lambda i: (i * i, 0))],
        out_specs=pl.BlockSpec((16, 64), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((64, 64), jnp.float32),
        interpret=True)(x)


def test_nonaffine_index_map_is_flagged_not_counted():
    args = (_f32(256, 64),)
    jaxpr = jax.make_jaxpr(_nonaffine)(*args)
    (eqn,) = [e for e in jaxpr.jaxpr.eqns
              if e.primitive.name == "pallas_call"]
    reason = unanalyzable_reason(eqn)
    assert isinstance(reason, PallasUnanalyzable)
    assert reason.reason == "non-affine-index-map"

    # the counter contributes NOTHING rather than fabricating traffic
    c = count_fn(_nonaffine, *args)
    assert BYTES_IN_FEATURE not in c and BYTES_OUT_FEATURE not in c
    assert not any(f.startswith("f_mem_contig") for f in c)

    # ... and the scope auditor reports the precise diagnostic
    diags = audit_callable(_nonaffine, args, "kernel:nonaffine")
    flagged = [d for d in diags if d.code == "pallas-unanalyzable"]
    assert len(flagged) == 1 and flagged[0].severity == "error"
    assert flagged[0].details["reason"] == "non-affine-index-map"
    assert not any(d.code == "opaque-primitive" for d in diags)


def test_analyzable_wrappers_audit_clean_of_pallas_codes():
    diags = audit_callable(
        functools.partial(ops.matmul, block_m=128, block_n=128,
                          block_k=128),
        (_f32(256, 256), _f32(256, 256)), "kernel:matmul")
    assert not any(d.code in ("opaque-primitive", "pallas-unanalyzable")
                   for d in diags)


# ---------------------------------------------------------------------------
# end-to-end: PerfSession prices a pallas wrapper with zero timings
# ---------------------------------------------------------------------------


def test_session_predicts_pallas_wrapper_with_memory_term():
    session = PerfSession.open(
        _profile(), timer=CountingTimer(lambda k, t: 0.125))
    fn = functools.partial(ops.matmul, block_m=128, block_n=128,
                           block_k=128)
    (pred,) = session.predict_batch([(fn, (_f32(256, 256), _f32(256, 256)))],
                                    names=["matmul"])
    assert session.timer.calls == 0
    assert pred.seconds > 0
    # the overlap model's memory operand is fed by the statically derived
    # block traffic — the memory term must carry real weight
    mem_terms = {k: v for k, v in pred.breakdown.items()
                 if "f_mem_contig_float32_load" in k}
    assert mem_terms and sum(mem_terms.values()) > 0


# ---------------------------------------------------------------------------
# grid-edge branches: pl.when charged to the programs that execute it
# ---------------------------------------------------------------------------


def _find_pallas_eqn(jaxpr):
    """The pallas_call equation anywhere under ``jaxpr`` (the wrappers
    jit, so it sits inside a pjit sub-jaxpr)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn
        for val in eqn.params.values():
            inner = getattr(val, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                found = _find_pallas_eqn(inner)
                if found is not None:
                    return found
    return None


@pytest.mark.parametrize("M,N,K,b", [
    (512, 512, 1024, 256),
    (256, 256, 512, 128),
])
def test_matmul_grid_edge_when_blocks_counted_exactly(M, N, K, b):
    """The accumulator init (``pl.when(k == 0)``) runs on gm·gn programs
    and the flush (``pl.when(k == n_k - 1)``) on another gm·gn — not on
    all P = gm·gn·nk.  Per-program predicate resolution makes the VMEM
    ref counts land on the exact closed form instead of the branch
    average."""
    fn = functools.partial(ops.matmul, block_m=b, block_n=b, block_k=b)
    c = count_fn(fn, _f32(M, K), _f32(K, N))
    gm, gn, nk = M // b, N // b, K // b
    P = gm * gn * nk
    # stores: every program stores the += accumulator; k==0 programs also
    # store the zero init; k==nk-1 programs store the o_ref write
    assert c["f_vmem_ref_float32_store"] == b * b * (P + 2 * gm * gn)
    # loads: a/b tiles + the += accumulator read on every program, plus
    # the flush's accumulator read on the last-k programs only
    assert c["f_vmem_ref_float32_load"] == b * b * (3 * P + gm * gn)
    # the += add itself runs on every program, edge blocks add nothing
    assert c["f_op_float32_add"] == b * b * P


def test_matmul_branch_resolution_emits_no_averaging_note():
    from repro.analysis.pallascost import analyze_pallas_call

    fn = functools.partial(ops.matmul, block_m=128, block_n=128,
                           block_k=128)
    jaxpr = jax.make_jaxpr(fn)(_f32(256, 256), _f32(256, 256))
    eqn = _find_pallas_eqn(jaxpr.jaxpr)
    assert eqn is not None
    cost = analyze_pallas_call(eqn)
    # both pl.when predicates are affine in program_id(2): resolved, not
    # averaged — the analyzer has nothing to warn about
    assert cost.notes == ()


def _data_dependent_when(x):
    def body(x_ref, o_ref):
        @pl.when(x_ref[0, 0] > 0.0)
        def _():
            o_ref[...] = x_ref[...] + 1.0

    return pl.pallas_call(
        body,
        grid=(4,),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
        interpret=True)(x)


def test_data_dependent_when_falls_back_to_average_with_note():
    from repro.analysis.pallascost import analyze_pallas_call

    args = (_f32(32, 128),)
    jaxpr = jax.make_jaxpr(_data_dependent_when)(*args)
    eqn = _find_pallas_eqn(jaxpr.jaxpr)
    cost = analyze_pallas_call(eqn)
    assert len(cost.notes) == 1
    assert "not a resolvable function of program_id" in cost.notes[0]
    # averaged: 4 programs × 1024 adds × 1/2 branch weight
    c = count_fn(_data_dependent_when, *args)
    assert c["f_op_float32_add"] == 4 * 8 * 128 // 2


def test_averaged_branch_surfaces_as_info_diagnostic():
    diags = audit_callable(_data_dependent_when, (_f32(32, 128),),
                           "kernel:ddwhen")
    flagged = [d for d in diags if d.code == "pallas-averaged-branch"]
    assert len(flagged) == 1 and flagged[0].severity == "info"
    assert "averaged" in flagged[0].message
    # resolvable grid-edge branches (matmul) must NOT trigger the note
    clean = audit_callable(
        functools.partial(ops.matmul, block_m=128, block_n=128,
                          block_k=128),
        (_f32(256, 256), _f32(256, 256)), "kernel:matmul")
    assert not any(d.code == "pallas-averaged-branch" for d in clean)
