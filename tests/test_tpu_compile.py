"""Ahead-of-time compiles of every ``kernels.ops`` wrapper for a described
TPU v5e, at the real widths ``chip_smoke.py`` runs them.  Nothing executes:
Mosaic's tiling and VMEM rules are checked by the chip's own compiler,
which refuses what interpret mode accepts.

The topology is described inside a fixture (never at import), so every
test worker collects the same tests and only the worker running this file
loads the TPU compiler.  The persistent compilation cache is off around
these compiles: an entry compiled for a described chip cannot be read back
without one.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

BF, F32 = jnp.bfloat16, jnp.float32

# name -> (wrapper, argument shapes as (shape, dtype) or a list of them)
CASES = {
    "matmul": (
        functools.partial(ops.matmul, block_m=512, block_n=512, block_k=512),
        [((4096, 4096), BF), ((4096, 4096), BF)]),
    # yi-6b: 32 q heads, 4 kv heads, head_dim 128, seq 4096
    "flash_attention": (
        functools.partial(ops.flash_attention, causal=True),
        [((1, 4096, 32, 128), BF), ((1, 4096, 4, 128), BF),
         ((1, 4096, 4, 128), BF)]),
    # zamba2-7b SSM: 112 heads of 64, d_state 64, chunk 256
    "mamba2_ssd": (
        functools.partial(ops.mamba2_ssd, chunk=256),
        [((1, 4096, 112, 64), BF), ((1, 4096, 112), F32),
         ((1, 4096, 112, 64), BF), ((1, 4096, 112, 64), BF)]),
    "stencil5": (ops.stencil5, [((4096, 4096), F32)]),
    # xlstm-125m sLSTM: 4 heads x 192, seq 2048, batch 8
    "slstm_cell": (
        ops.slstm_cell,
        [((8, 2048, 4, 4, 192), F32), ((4, 192, 4, 192), F32),
         ((4, 4, 192), F32)]),
    # the study battery's largest stream / madd / dg sizes
    "stream_strided": (
        functools.partial(ops.stream_strided, stride=2),
        [[((1 << 24,), F32)] * 4]),
    "madd_throughput": (
        functools.partial(ops.madd_throughput, iters=512),
        [((65536,), F32)]),
    "dg_diff": (ops.dg_diff, [((3, 64, 64), F32), ((64, 65536), F32)]),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _specs(arg, sharding):
    if isinstance(arg, list):
        return [_specs(a, sharding) for a in arg]
    shape, dtype = arg
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_every_ops_wrapper_has_a_case():
    public = {n for n in dir(ops) if not n.startswith("_")
              and isinstance(getattr(ops, n), type(ops.matmul))}
    assert public == set(CASES)


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, args = CASES[name]
    compiled = jax.jit(functools.partial(fn, interpret=False)).lower(
        *_specs(args, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
