"""Built-in lint targets: the repo's Pallas kernel wrappers
(:mod:`repro.kernels.ops`) at their canonical test shapes.

Arguments are :class:`jax.ShapeDtypeStruct` values from the start — no
device arrays are ever built, so ``repro.lint --kernels`` audits the
whole kernel surface with zero allocations and zero executions.  Shapes
mirror ``tests/test_kernels.py`` (one representative configuration per
kernel); block sizes are bound statically via ``functools.partial`` the
same way the tests call them.

These audits are EXPECTED to be clean: ``pallas_call`` is no longer
opaque — the static cost analyzer (:mod:`repro.analysis.pallascost`)
opens every wrapper here, audits the kernel-body jaxpr with the ordinary
scope vocabulary, and serves grid-scaled counts plus block-spec HBM
traffic to the counter.  The checked-in ``lint_baseline.json`` is
therefore EMPTY; any error on these targets is a regression.  A
``pallas_call`` the analyzer cannot open (dynamic grid, non-affine index
map, scalar prefetch) surfaces as the precise ``pallas-unanalyzable``
diagnostic instead of a blanket opacity error.

The same names feed ``python -m repro.calibrate predict --kernel NAME``:
each target predicts end-to-end from a saved profile with zero timings,
its memory term attributed from the statically derived traffic.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, List, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class KernelTarget:
    """One audit target: a callable plus ALREADY-abstract arguments
    (``ShapeDtypeStruct`` leaves — pass straight to ``jax.make_jaxpr``)."""

    name: str
    fn: Callable = field(repr=False)
    args: Tuple[Any, ...] = field(repr=False)


def _f32(*shape) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def kernel_targets() -> List[KernelTarget]:
    """The built-in target set behind ``repro.lint --kernels``."""
    from repro.kernels import ops

    return [
        KernelTarget(
            "kernels.ops.matmul",
            functools.partial(ops.matmul, block_m=128, block_n=128,
                              block_k=128),
            (_f32(128, 128), _f32(128, 128))),
        KernelTarget(
            "kernels.ops.flash_attention",
            functools.partial(ops.flash_attention, causal=True,
                              block_q=64, block_k=64),
            (_f32(2, 256, 8, 64), _f32(2, 256, 2, 64),
             _f32(2, 256, 2, 64))),
        KernelTarget(
            "kernels.ops.mamba2_ssd",
            functools.partial(ops.mamba2_ssd, chunk=32),
            (_f32(2, 128, 4, 32), _f32(2, 128, 4),
             _f32(2, 128, 4, 16), _f32(2, 128, 4, 16))),
        KernelTarget(
            "kernels.ops.stencil5",
            functools.partial(ops.stencil5, block_m=128, block_n=128),
            (_f32(256, 256),)),
        KernelTarget(
            "kernels.ops.dg_diff",
            functools.partial(ops.dg_diff, block_e=256),
            (_f32(3, 64, 64), _f32(64, 1024))),
        KernelTarget(
            "kernels.ops.stream_strided",
            functools.partial(ops.stream_strided, block=1024, stride=2),
            ([_f32(8192), _f32(8192)],)),
        KernelTarget(
            "kernels.ops.madd_throughput",
            functools.partial(ops.madd_throughput, iters=32, block=1024),
            (_f32(4096),)),
        KernelTarget(
            "kernels.ops.slstm_cell",
            ops.slstm_cell,
            (_f32(2, 32, 4, 4, 16), _f32(4, 16, 4, 16), _f32(4, 4, 16))),
    ]
