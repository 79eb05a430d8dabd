"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``python -m repro.calibrate`` / ``serve`` / ``tune``,
``repro.launch.train`` and ``chip_smoke.py``) call :func:`enable` from
``main()``; nothing calls it at import.  Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and this module sets no other directory.
Otherwise the cache lives at ``<checkout>/.jax_cache``: a fixed path, so
a later run in the same checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the repository checkout holding ``src/repro``
CHECKOUT = Path(__file__).resolve().parents[2]


def cache_dir() -> str:
    """Where :func:`enable` puts the cache."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT / ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on; returns its directory."""
    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    # the calibration battery is hundreds of sub-second compiles, all under
    # JAX's default 1 s floor for caching; with the floor left in place a
    # warm run would recompile the whole battery
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
