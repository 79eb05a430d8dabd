"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle.

The wrappers compile the Mosaic kernel by default; on this CPU host each
test asks for the interpreter explicitly."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def rn(*shape, dtype=jnp.float32, i=0):
    return jax.random.normal(jax.random.fold_in(KEY, i), shape,
                             jnp.float32).astype(dtype)


TOL = {jnp.float32: dict(rtol=2e-4, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _close(a, b, dtype):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), **TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (128, 128, 128, 128, 128, 128),
    (256, 128, 512, 128, 128, 64),
    (512, 512, 256, 256, 128, 256),
])
def test_matmul_tiled(dtype, m, k, n, bm, bn, bk):
    a, b = rn(m, k, dtype=dtype, i=1), rn(k, n, dtype=dtype, i=2)
    _close(ops.matmul(a, b, block_m=bm, block_n=bn, block_k=bk,
                      interpret=True),
           ref.matmul_ref(a, b), dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kw", [
    dict(causal=True),
    dict(causal=False),
    dict(causal=True, window=64),
    dict(causal=True, softcap=30.0),
    dict(causal=True, window=32, softcap=50.0),
])
@pytest.mark.parametrize("B,S,Hq,Hkv,D", [
    (2, 256, 8, 2, 64),    # GQA 4:1
    (1, 128, 4, 4, 128),   # MHA
    (2, 512, 8, 1, 64),    # MQA
])
def test_flash_attention(dtype, kw, B, S, Hq, Hkv, D):
    q = rn(B, S, Hq, D, dtype=dtype, i=3)
    k = rn(B, S, Hkv, D, dtype=dtype, i=4)
    v = rn(B, S, Hkv, D, dtype=dtype, i=5)
    out = ops.flash_attention(q, k, v, block_q=64, block_k=64,
                              interpret=True, **kw)
    _close(out, ref.attention_ref(q, k, v, **kw), dtype)


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (2, 128, 4, 32, 16, 32),
    (1, 256, 2, 64, 64, 64),
    (2, 64, 8, 16, 32, 16),
])
def test_mamba2_ssd(B, S, H, P, N, chunk):
    xdt = rn(B, S, H, P, i=6)
    da = -jnp.abs(rn(B, S, H, i=7)) * 0.1
    Bm, Cm = rn(B, S, H, N, i=8), rn(B, S, H, N, i=9)
    out = ops.mamba2_ssd(xdt, da, Bm, Cm, chunk=chunk, interpret=True)
    _close(out, ref.ssd_ref(xdt, da, Bm, Cm), jnp.float32)


@pytest.mark.parametrize("m,n,bm,bn", [
    (256, 256, 128, 128), (256, 512, 256, 256), (128, 128, 64, 128)])
def test_stencil5(m, n, bm, bn):
    u = rn(m, n, i=10)
    _close(ops.stencil5(u, block_m=bm, block_n=bn, interpret=True),
           ref.stencil5_ref(u), jnp.float32)


@pytest.mark.parametrize("M,N,K,be", [(3, 64, 1024, 256), (1, 32, 512, 512)])
def test_dg_diff(M, N, K, be):
    dm, ut = rn(M, N, N, i=11), rn(N, K, i=12)
    _close(ops.dg_diff(dm, ut, block_e=be, interpret=True),
           ref.dg_diff_ref(dm, ut), jnp.float32)


@pytest.mark.parametrize("stride", [1, 2, 4])
@pytest.mark.parametrize("n_arrays", [1, 3])
def test_stream_strided(stride, n_arrays):
    arrs = [rn(8192, i=20 + j) for j in range(n_arrays)]
    _close(ops.stream_strided(arrs, block=1024, stride=stride,
                              interpret=True),
           ref.stream_ref(arrs, block=1024, stride=stride), jnp.float32)


def test_madd_throughput():
    x = rn(4096, i=30)
    _close(ops.madd_throughput(x, iters=32, block=1024, interpret=True),
           ref.madd_ref(x, iters=32), jnp.float32)


def test_flash_vs_model_blockwise():
    """The Pallas kernel and the model library's jnp blockwise path are the
    same contraction — they must agree bitwise-closely."""
    from repro.models.layers import blockwise_attention

    q, k, v = rn(2, 256, 8, 64, i=40), rn(2, 256, 2, 64, i=41), \
        rn(2, 256, 2, 64, i=42)
    a = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                            interpret=True)
    b = blockwise_attention(q, k, v, causal=True, q_chunk=64, kv_chunk=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize("B,S,H,dh", [(2, 32, 4, 16), (1, 48, 2, 32)])
def test_slstm_cell_kernel(B, S, H, dh):
    g_in = rn(B, S, 4, H, dh, i=50) * 0.5
    r = rn(H, dh, 4, dh, i=51) * 0.1
    b = rn(4, H, dh, i=52) * 0.1
    out = ops.slstm_cell(g_in, r, b, interpret=True)
    want = ref.slstm_cell_ref(g_in, r, b)
    _close(out, want, jnp.float32)


def test_slstm_cell_rejects_partial_tile():
    g_in = jax.ShapeDtypeStruct((2, 24, 4, 4, 16), jnp.float32)
    r = jax.ShapeDtypeStruct((4, 16, 4, 16), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 4, 16), jnp.float32)
    with pytest.raises(ValueError, match="multiple"):
        jax.make_jaxpr(ops.slstm_cell)(g_in, r, b)


def test_cpu_execution_without_interpret_raises():
    """No silent interpreter fallback: a compiled kernel on a CPU backend
    fails loudly, while tracing it for static pricing still works."""
    a = rn(128, 128, i=60)
    jax.make_jaxpr(ops.matmul)(a, a)
    if jax.default_backend() == "tpu":
        pytest.skip("a TPU backend compiles the kernel")
    with pytest.raises(ValueError, match="interpret"):
        jax.block_until_ready(ops.matmul(a, a, block_m=128, block_n=128,
                                         block_k=128))
