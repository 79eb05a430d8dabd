"""Trip-count-aware HLO cost walker: the roofline's measurement instrument."""
import jax
import jax.numpy as jnp
import pytest

from repro.core.hlo import HloCostAnalyzer, parse_hlo


def _analyze(fn, *specs, n_dev=1, **jit_kw):
    txt = jax.jit(fn, **jit_kw).lower(*specs).compile().as_text()
    return HloCostAnalyzer(txt, num_devices=n_dev).entry_cost()


def test_scan_flops_multiplied_by_trip_count():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    s = jax.ShapeDtypeStruct((512, 512), jnp.bfloat16)
    c = _analyze(f, s, s)
    expect = 10 * 2 * 512 ** 3
    assert abs(c.flops - expect) / expect < 0.02
    # XLA's own analysis visits the body once → ~10× undercount
    xla = jax.jit(f).lower(s, s).compile().cost_analysis()["flops"]
    assert xla < c.flops / 5


def test_nested_scan_multiplies():
    def f(x):
        def outer(c, _):
            def inner(ci, _):
                return ci * 1.5 + 1.0, None
            ci, _ = jax.lax.scan(inner, c, None, length=4)
            return ci, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    c = _analyze(f, jax.ShapeDtypeStruct((128, 128), jnp.float32))
    # 3 × 4 × (mul + add) per element
    expect = 3 * 4 * 2 * 128 * 128
    assert abs(c.flops - expect) / expect < 0.35  # loop plumbing adds a bit


def test_dot_flops_from_contracting_dims():
    def f(a, b):
        return jnp.einsum("bik,bkj->bij", a, b)

    c = _analyze(f, jax.ShapeDtypeStruct((4, 64, 96), jnp.float32),
                 jax.ShapeDtypeStruct((4, 96, 32), jnp.float32))
    expect = 2 * 4 * 64 * 32 * 96
    assert abs(c.flops - expect) / expect < 0.05


def test_scan_slice_fusion_bytes_not_full_array():
    """A scan reading one row per step must not be charged the full array
    per step (the fusion slice-awareness fix)."""
    def f(xs):
        def body(c, i):
            row = jax.lax.dynamic_slice(xs, (i, 0), (1, 1024))
            return c + jnp.sum(row), None
        c, _ = jax.lax.scan(body, jnp.float32(0), jnp.arange(1024))
        return c

    c = _analyze(f, jax.ShapeDtypeStruct((1024, 1024), jnp.float32))
    full_per_step = 1024 * 1024 * 1024 * 4  # the bug would charge this
    assert c.bytes < full_per_step / 50
    assert c.bytes > 1024 * 1024 * 4 * 0.5  # but at least ~one full pass


def test_collective_detection_and_wire_bytes():
    import os
    # collectives need >1 device; spawn via subprocess to isolate device cnt
    import subprocess
    import sys
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
import sys
sys.path.insert(0, "src")
from repro.launch.mesh import make_mesh
from repro.core.hlo import HloCostAnalyzer
mesh = make_mesh((8,), ("d",))
def f(x):
    return jnp.sum(x)
jf = jax.jit(f, in_shardings=NamedSharding(mesh, P("d")))
txt = jf.lower(jax.ShapeDtypeStruct((1024, 64), jnp.float32)).compile().as_text()
c = HloCostAnalyzer(txt, num_devices=8).entry_cost()
assert c.coll_count.get("all-reduce", 0) >= 1, c.as_dict()
print("WIRE", c.collective_wire_bytes)
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=os.getcwd())
    assert out.returncode == 0, out.stderr[-2000:]
    assert "WIRE" in out.stdout


def test_parse_hlo_structure():
    def f(a, b):
        return jnp.tanh(a @ b)

    s = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    txt = jax.jit(f).lower(s, s).compile().as_text()
    comps, entry = parse_hlo(txt)
    assert entry is not None
    assert any(op.opcode == "dot" for c in comps.values() for op in c.ops)
