"""Work-removal code transformation (paper §7.1.1, Algorithm 3), on jaxprs.

The paper strips arithmetic and local-memory operations from a kernel while
keeping a selected set of global memory accesses *with their loop
environment intact*, accumulating the kept loads into ``tgt_read`` and
storing it so the compiler cannot dead-code-eliminate the access.

The JAX realization interprets a ClosedJaxpr with a rewriting evaluator:

  * control flow (``scan``/``cond``/``jit``/``remat``) is preserved by
    recursing into sub-jaxprs — loop environments (and therefore per-
    iteration access counts / AFR) survive,
  * compute equations (``dot_general``, transcendentals, mul/div, …) are
    replaced by a cheap proxy: the output becomes
    ``zeros(shape) + Σ reduce_sum(kept operands)`` — each kept operand is
    still *read in full, once per execution of the site*, but the O(n·m)
    arithmetic is gone (additive accounting, exactly Algorithm 3's
    ``tgt_read = tgt_read + g_ld``),
  * operands whose lineage traces only to *removed* arrays contribute
    nothing, and jit DCE then eliminates their loads,
  * the scalar accumulator is returned (the ``tgt_read_dest`` store).

Deviation from the paper (recorded in DESIGN.md): the final store writes one
scalar per *kernel* rather than one element per work-item — on TPU the
no-DCE guarantee needs only a data dependence to a live output.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Sequence, Set

import jax
import jax.numpy as jnp

from repro.core.counting import CALL_PRIMITIVES

# primitives whose *computation* is stripped (memory reads of their kept
# operands are preserved through the reduce_sum proxy)
COMPUTE_PRIMS: Set[str] = {
    "dot_general", "conv_general_dilated", "exp", "log", "tanh", "logistic",
    "pow", "integer_pow", "sqrt", "rsqrt", "erf", "sin", "cos", "mul", "div",
    "rem", "atan2", "expm1", "log1p", "exp2", "cumsum", "cumprod",
    "cumlogsumexp", "erf_inv", "lgamma", "digamma",
}

# primitives kept verbatim — they *are* the memory accesses / loop plumbing
_STRUCTURAL = True


def _is_float(aval) -> bool:
    return jnp.issubdtype(aval.dtype, jnp.floating)


def _proxy_read(x) -> jax.Array:
    """Read every element of ``x`` once, additively (tgt_read += Σx)."""
    return jnp.sum(x.astype(jnp.float32)) if hasattr(x, "astype") \
        else jnp.float32(0)


def remove_work(
    fn: Callable,
    *example_args,
    remove_args: Sequence[int] = (),
) -> Callable:
    """Build the stripped kernel for ``fn``.

    ``remove_args``: positional indices of array arguments whose accesses
    should be removed (the paper's ``remove_vars``).  The returned callable
    has the *same signature* (removed args are accepted and ignored, so
    timing harnesses can reuse the argument builders) and returns a scalar
    ``tgt_read`` accumulator.
    """
    closed = jax.make_jaxpr(fn)(*example_args)
    removed = set(remove_args)

    def stripped(*args):
        consts = closed.consts
        env: Dict[Any, Any] = {}
        dead: set = set()  # vars whose lineage is entirely removed arrays

        def read(var):
            from jax._src.core import Literal

            if isinstance(var, Literal):
                return var.val
            return env[var]

        def write(var, val):
            env[var] = val

        jaxpr = closed.jaxpr
        for cv, c in zip(jaxpr.constvars, consts):
            write(cv, c)
        # removed inputs become constants-of-zeros; dead-lineage propagation
        # below keeps their (now meaningless) access chains out of the
        # feature counts entirely
        for i, (iv, a) in enumerate(zip(jaxpr.invars, args)):
            if i in removed:
                write(iv, jnp.zeros(iv.aval.shape, iv.aval.dtype))
                dead.add(iv)
            else:
                write(iv, a)

        acc = _eval_jaxpr_stripped(jaxpr, read, write, dead)
        return acc

    return stripped


def _eval_jaxpr_stripped(jaxpr, read, write, dead=None) -> jax.Array:
    """Interpret, replacing compute eqns by the additive-read proxy.

    Returns the ``tgt_read`` accumulator for this jaxpr body.
    """
    from jax._src.core import Literal

    dead = dead if dead is not None else set()
    acc = jnp.float32(0)
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        arr_invars = [v for v in eqn.invars
                      if not isinstance(v, Literal) and v.aval.shape]
        all_dead = bool(arr_invars) and all(v in dead for v in arr_invars)
        invals = [read(v) for v in eqn.invars]

        # index/integer arithmetic is structural (it *defines* the access
        # patterns of the kept loads) — never strip it
        is_float_out = eqn.outvars and _is_float(eqn.outvars[0].aval)

        if prim in COMPUTE_PRIMS and is_float_out:
            contrib = jnp.float32(0)
            for v, val in zip(eqn.invars, invals):
                if isinstance(v, Literal) or v in dead:
                    continue  # removed lineage contributes no read
                if hasattr(val, "dtype") and jnp.issubdtype(
                        jnp.asarray(val).dtype, jnp.floating):
                    contrib = contrib + _proxy_read(val)
            acc = acc + contrib
            for ov in eqn.outvars:
                proxy = jnp.zeros(ov.aval.shape, ov.aval.dtype)
                # keep a (broadcast, O(1)-read) data dependence on the reads
                if _is_float(ov.aval):
                    proxy = proxy + contrib.astype(ov.aval.dtype)
                write(ov, proxy)
            continue

        if all_dead and prim not in ("scan", "cond", "while",
                                     *CALL_PRIMITIVES):
            # access chain of a removed array: emit zeros, mark dead —
            # the load disappears from the stripped kernel's features too
            for ov in eqn.outvars:
                write(ov, jnp.zeros(ov.aval.shape, ov.aval.dtype))
                dead.add(ov)
            continue

        if prim == "scan":
            inner = eqn.params["jaxpr"]
            length = eqn.params["length"]
            n_carry = eqn.params["num_carry"]
            n_consts = eqn.params["num_consts"]
            consts = invals[:n_consts]
            carry = invals[n_consts:n_consts + n_carry]
            xs = invals[n_consts + n_carry:]
            inner_dead_idx = [i for i, v in enumerate(eqn.invars)
                              if not isinstance(v, Literal) and v in dead]

            def body(c, x):
                c_acc, c_carry = c
                sub_env: Dict[Any, Any] = {}

                def sread(var):
                    from jax._src.core import Literal

                    if isinstance(var, Literal):
                        return var.val
                    return sub_env[var]

                def swrite(var, val):
                    sub_env[var] = val

                ij = inner.jaxpr
                for cv, cc in zip(ij.constvars, inner.consts):
                    swrite(cv, cc)
                allin = list(consts) + list(c_carry) + list(x)
                for iv, a in zip(ij.invars, allin):
                    swrite(iv, a)
                sub_dead = {ij.invars[i] for i in inner_dead_idx}
                a2 = _eval_jaxpr_stripped(ij, sread, swrite, sub_dead)
                outs = [sread(ov) for ov in ij.outvars]
                new_carry = outs[:n_carry]
                ys = outs[n_carry:]
                return (c_acc + a2, tuple(new_carry)), tuple(ys)

            (acc, carry_out), ys = jax.lax.scan(
                body, (acc, tuple(carry)), tuple(xs), length=length)
            outs = list(carry_out) + list(ys)
            for ov, o in zip(eqn.outvars, outs):
                write(ov, o)
            continue

        if prim in CALL_PRIMITIVES:
            sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            ij = sub.jaxpr if hasattr(sub, "jaxpr") else sub
            sub_env: Dict[Any, Any] = {}

            def sread(var):
                from jax._src.core import Literal

                if isinstance(var, Literal):
                    return var.val
                return sub_env[var]

            def swrite(var, val):
                sub_env[var] = val

            consts2 = sub.consts if hasattr(sub, "consts") else []
            for cv, cc in zip(ij.constvars, consts2):
                swrite(cv, cc)
            for iv, a in zip(ij.invars, invals):
                swrite(iv, a)
            sub_dead = {iv for iv, v in zip(ij.invars, eqn.invars)
                        if not isinstance(v, Literal) and v in dead}
            acc = acc + _eval_jaxpr_stripped(ij, sread, swrite, sub_dead)
            for ov, iv_out in zip(eqn.outvars, ij.outvars):
                write(ov, sread(iv_out))
            continue

        # structural / memory primitives: evaluate verbatim
        out = eqn.primitive.bind(*invals, **eqn.params)
        if eqn.primitive.multiple_results:
            for ov, o in zip(eqn.outvars, out):
                write(ov, o)
        else:
            write(eqn.outvars[0], out)

    # fold the jaxpr's own float outputs into the accumulator (negligible
    # weight) so every kept load chain stays live under DCE
    for ov in jaxpr.outvars:
        from jax._src.core import Literal

        if isinstance(ov, Literal):
            continue
        v = read(ov)
        if hasattr(v, "dtype") and jnp.issubdtype(v.dtype, jnp.floating):
            acc = acc + 1e-30 * jnp.sum(v.astype(jnp.float32))
    return acc
