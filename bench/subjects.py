"""A configuration's subjects, built through the program's normal path.

A subject spec is a dict in the configuration file: ``kind`` picks one of
the functions below and the rest are its sizes.  Without a key,
:func:`build` gives the callable with ``ShapeDtypeStruct`` arguments (what
a price request sends); with one, the callable with arrays drawn from the
key on the device (what set-up times).  Request sizes (``batch``, ``seq``, ``tokens``) override the
spec's.  Every subject is a Pallas kernel of ``repro.kernels.ops`` at the
configuration's published widths.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

S = jax.ShapeDtypeStruct

def _interp(spec) -> bool:
    """Pallas kernels run compiled; a CPU test asks for the interpreter."""
    return bool(spec.get("interpret", False))


def _arrays(key, shapes):
    if key is None:
        return tuple(S(s, d) for s, d, _ in shapes)
    out = []
    for i, (s, d, scale) in enumerate(shapes):
        x = jax.random.normal(jax.random.fold_in(key, i), s, jnp.float32)
        out.append((x * scale).astype(d))
    return tuple(out)


def slstm_cell(spec, *, batch, seq, key=None):
    from repro.kernels import ops

    h, d = int(spec["heads"]), int(spec["head_dim"])
    f32 = jnp.float32
    args = _arrays(key, [((batch, seq, 4, h, d), f32, 0.5),
                         ((h, d, 4, d), f32, 0.1), ((4, h, d), f32, 0.1)])
    return functools.partial(ops.slstm_cell, interpret=_interp(spec)), args


def _sequences(tokens: int, chunk: int, max_seq: int) -> int:
    """The fewest sequences, each a whole number of chunks and no longer
    than ``max_seq``, that ``tokens`` splits into evenly."""
    for b in range(1, tokens // chunk + 1):
        if tokens % b == 0 and (tokens // b) % chunk == 0 \
                and tokens // b <= max_seq:
            return b
    raise ValueError(f"{tokens} tokens split into no sequences of whole "
                     f"{chunk}-token chunks up to {max_seq}")


def mamba2_ssd(spec, *, tokens, key=None):
    """B and C are per head: the configuration's groups broadcast to its
    heads before the call, as the kernel takes them."""
    from repro.kernels import ops

    h, d, n = int(spec["heads"]), int(spec["head_dim"]), int(spec["d_state"])
    chunk = int(spec["chunk"])
    b = _sequences(tokens, chunk, int(spec.get("max_seq", tokens)))
    t = tokens // b
    bf = jnp.bfloat16
    args = _arrays(key, [((b, t, h, d), bf, 1.0),
                         ((b, t, h), jnp.float32, 0.1),
                         ((b, t, h, n), bf, 1.0),
                         ((b, t, h, n), bf, 1.0)])
    if key is not None:
        args = (args[0], -jnp.abs(args[1]), args[2], args[3])
    return functools.partial(ops.mamba2_ssd, chunk=chunk,
                             interpret=_interp(spec)), args


def matmul(spec, *, tokens, key=None):
    from repro.kernels import ops

    k, n, blk = int(spec["k"]), int(spec["n"]), int(spec["block"])
    dt = jnp.dtype(spec["dtype"])
    args = _arrays(key, [((tokens, k), dt, 1.0), ((k, n), dt, 1.0)])
    return functools.partial(ops.matmul, block_m=blk, block_n=blk,
                             block_k=blk, interpret=_interp(spec)), args


BUILDERS: Dict[str, Callable] = {
    "slstm_cell": slstm_cell, "mamba2_ssd": mamba2_ssd, "matmul": matmul,
}


def build(spec: Dict[str, Any], key=None, **sizes):
    """``(fn, args)`` of one subject: abstract without ``key``, arrays on
    the device drawn from ``key`` with it."""
    kind = spec["kind"]
    if kind not in BUILDERS:
        raise KeyError(f"no subject kind {kind!r}; known: "
                       f"{sorted(BUILDERS)}")
    params = {k: spec[k] for k in ("batch", "seq", "tokens") if k in spec}
    params.update(sizes)
    return BUILDERS[kind](spec, key=key, **params)
