"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* first init.

Every mesh axis is ``AxisType.Auto``: the compiler propagates shardings
from the logical-axis rules rather than each op naming its own.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 = 256 chips per pod; 2 pods = 512 chips when ``multi_pod``."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], *,
              devices: Optional[Sequence] = None) -> Mesh:
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_host_mesh(model: Optional[int] = None, *,
                   devices: Optional[Sequence] = None) -> Mesh:
    """(data, model) mesh over ``devices`` (default: every device)."""
    devices = list(devices) if devices is not None else jax.devices()
    n = len(devices)
    model = model or 1
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"), devices=devices)
