"""The persistent compilation cache goes where the environment says, or to
the checkout's fixed ``.jax_cache`` — and nowhere else."""
import os
import subprocess
import sys
import uuid
from pathlib import Path

import pytest

from repro import compile_cache

_PROBE = (
    "import sys\n"
    "from repro import compile_cache\n"
    "print(compile_cache.enable())\n"
    "import jax, jax.numpy as jnp\n"
    # a constant no other run uses: the program is new to every cache
    "c = float(sys.argv[1])\n"
    "jax.jit(lambda x: x * c + 1)(jnp.ones(8)).block_until_ready()\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
)


def _entries(where: Path) -> set:
    return {p.name for p in where.iterdir()} if where.is_dir() else set()


def _probe(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop(compile_cache.ENV_VAR, None)
    if env_dir is not None:
        env[compile_cache.ENV_VAR] = str(env_dir)
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [str(compile_cache.CHECKOUT / "src"),
                      env.get("PYTHONPATH")]))
    out = subprocess.run(
        [sys.executable, "-c", _PROBE, str(uuid.uuid4().int % 10**6 + 2)],
        env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_default_is_the_checkout_cache(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.cache_dir() == str(
        Path(compile_cache.__file__).resolve().parents[2] / ".jax_cache")
    assert (compile_cache.CHECKOUT / "src" / "repro").is_dir()


@pytest.mark.parametrize("from_env", [True, False])
def test_entries_land_in_one_directory(tmp_path, from_env):
    default = compile_cache.CHECKOUT / ".jax_cache"
    where = tmp_path / "x" if from_env else default
    other = default if from_env else tmp_path / "x"
    before, other_before = _entries(where), _entries(other)
    assert _probe(where if from_env else None) == [str(where), str(where)]
    assert _entries(where) - before, "no cache entry was written"
    assert _entries(other) == other_before
