"""Expert-parallel MoE dispatch via shard_map + all-to-all (§Perf H1).

The baseline ``apply_moe`` scatters tokens into a global ``[E, C, D]``
buffer; under pjit the data-dependent scatter/gather forces XLA to
all-gather activations *and* expert weights (measured: deepseek-v2
train_4k spends 3× more wire time than HBM time, and the buffers blow the
per-chip HBM budget).

This path is the production layout (GShard/Switch):

  1. tokens are sharded over BOTH the dp axes and the EP ("model") axis —
     inside shard_map each device routes its own T_loc tokens,
  2. each device buckets its tokens by *destination EP rank* (the rank
     owning the target expert) into fixed-capacity send buffers
     ``[ep, C_pair, D]``,
  3. one ``all_to_all`` over the EP axis delivers every token to its
     expert's owner; a local sort buckets by local expert,
  4. local expert FFN ``[E_loc, C_loc, D]``,
  5. the reverse ``all_to_all`` returns outputs; gates are applied locally.

Wire cost per layer: 2 × T·k·cf·D·bytes / chips — independent of E — vs
the baseline's all-gathers of the full activation + weight tensors.
Differentiable end-to-end (all_to_all transposes to all_to_all).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.sharding import current_mesh


def _ep_axis(mesh) -> Optional[str]:
    return "model" if mesh is not None and "model" in mesh.shape else None


def _dp_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def apply_moe_a2a(p: Dict, cfg: ModelConfig, x: jax.Array
                  ) -> Tuple[jax.Array, Dict]:
    """Drop-in replacement for ``apply_moe`` on a (pod,data,model) mesh."""
    mesh = current_mesh()
    ep = _ep_axis(mesh)
    if ep is None or cfg.moe.num_experts % mesh.shape[ep] != 0:
        from repro.models.moe import apply_moe

        return apply_moe(p, cfg, x)

    m = cfg.moe
    B, S, D = x.shape
    n_ep = mesh.shape[ep]
    E_loc = m.num_experts // n_ep
    dp = _dp_axes(mesh)

    x_spec = P(dp if dp else None, None, None)
    # expert weights: E sharded over the EP axis
    w_spec = P(ep)
    router_spec = P()

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(x_spec, router_spec, w_spec, w_spec, w_spec),
        out_specs=(x_spec, P(), P()),
        check_vma=False,
    )
    def dispatch(x_blk, router, w_gate, w_up, w_down):
        # x_blk: [B_loc, S, D] — identical across EP ranks; each EP rank
        # processes its 1/n_ep slice of the local tokens.
        ep_rank = jax.lax.axis_index(ep)
        Bl, S_, D_ = x_blk.shape
        T_all = Bl * S_
        # pad token count to an EP multiple (decode batches can be tiny)
        T_pad = -(-T_all // n_ep) * n_ep
        xf = x_blk.reshape(T_all, D_)
        if T_pad != T_all:
            xf = jnp.pad(xf, ((0, T_pad - T_all), (0, 0)))
        T_loc = T_pad // n_ep
        x_my = jax.lax.dynamic_slice_in_dim(xf, ep_rank * T_loc, T_loc, 0)

        # ----- local routing ------------------------------------------------
        logits = x_my.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)  # [T_loc, E]
        gate_vals, eidx = jax.lax.top_k(probs, m.top_k)
        gate_vals = gate_vals / jnp.clip(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
        me = jnp.mean(probs, axis=0)
        assign = jnp.mean(jnp.sum(
            jax.nn.one_hot(eidx, m.num_experts, dtype=jnp.float32), 1), 0)
        aux = m.num_experts * jnp.sum(me * assign)
        aux = jax.lax.pmean(aux, ep)
        for a in dp:
            aux = jax.lax.pmean(aux, a)

        # ----- bucket by destination EP rank --------------------------------
        K = m.top_k
        e_flat = eidx.reshape(-1)                    # [T_loc*K]
        dst = e_flat // E_loc                        # owning EP rank
        t_flat = jnp.repeat(jnp.arange(T_loc), K)
        g_flat = gate_vals.reshape(-1)
        order = jnp.argsort(dst, stable=True)
        dst_s, e_s, t_s, g_s = dst[order], e_flat[order], t_flat[order], \
            g_flat[order]
        # capacity per (src, dst) pair
        C_pair = max(8, -(-int(T_loc * K * m.capacity_factor / n_ep) // 8) * 8)
        start = jnp.searchsorted(dst_s, jnp.arange(n_ep), side="left")
        rank_in = jnp.arange(T_loc * K) - start[dst_s]
        keep = rank_in < C_pair
        slot = jnp.where(keep, dst_s * C_pair + rank_in, n_ep * C_pair)

        send_x = jnp.zeros((n_ep * C_pair + 1, D_), x_blk.dtype)
        send_x = send_x.at[slot].set(x_my[t_s].astype(x_blk.dtype))
        send_e = jnp.full((n_ep * C_pair + 1,), -1, jnp.int32).at[slot].set(
            e_s.astype(jnp.int32))
        send_x = send_x[:-1].reshape(n_ep, C_pair, D_)
        send_e = send_e[:-1].reshape(n_ep, C_pair)

        # ----- all-to-all: deliver to expert owners -------------------------
        recv_x = jax.lax.all_to_all(send_x, ep, 0, 0, tiled=False)
        recv_e = jax.lax.all_to_all(send_e, ep, 0, 0, tiled=False)
        # recv_*: [n_ep(src), C_pair, D] — tokens for MY experts

        # ----- local bucketing by local expert -------------------------------
        R = n_ep * C_pair
        rx = recv_x.reshape(R, D_)
        re = recv_e.reshape(R)
        le = jnp.where(re >= 0, re - ep_rank * E_loc, E_loc)  # local expert id
        order2 = jnp.argsort(le, stable=True)
        le_s = le[order2]
        C_loc = max(8, -(-int(R * 2 / max(E_loc, 1)) // 8) * 8)
        start2 = jnp.searchsorted(le_s, jnp.arange(E_loc), side="left")
        rank2 = jnp.arange(R) - start2[jnp.minimum(le_s, E_loc - 1)]
        keep2 = (le_s < E_loc) & (rank2 < C_loc)
        slot2 = jnp.where(keep2, le_s * C_loc + rank2, E_loc * C_loc)
        buf = jnp.zeros((E_loc * C_loc + 1, D_), x_blk.dtype)
        buf = buf.at[slot2].set(rx[order2])
        buf = buf[:-1].reshape(E_loc, C_loc, D_)

        # ----- expert FFN (local weights) ------------------------------------
        up = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(buf.dtype))
        gate = layers._act(cfg.activation, jnp.einsum(
            "ecd,edf->ecf", buf, w_gate.astype(buf.dtype)))
        out_buf = jnp.einsum("ecf,efd->ecd", gate * up,
                             w_down.astype(buf.dtype))

        # ----- un-bucket + reverse all-to-all --------------------------------
        out_flat = out_buf.reshape(E_loc * C_loc, D_)
        contrib = out_flat[jnp.minimum(slot2, E_loc * C_loc - 1)] \
            * keep2[:, None].astype(out_flat.dtype)
        back = jnp.zeros((R, D_), x_blk.dtype).at[order2].set(contrib)
        back = back.reshape(n_ep, C_pair, D_)
        ret_x = jax.lax.all_to_all(back, ep, 0, 0, tiled=False)
        # ret_x: [n_ep(dst), C_pair, D] — this rank's tokens, back home

        # ----- combine with gates --------------------------------------------
        ret_flat = ret_x.reshape(n_ep * C_pair, D_)
        y_my = jnp.zeros((T_loc, D_), x_blk.dtype)
        gathered = ret_flat[jnp.minimum(slot, n_ep * C_pair - 1)] \
            * (keep * g_s)[:, None].astype(x_blk.dtype)
        y_my = y_my.at[t_s].add(gathered)

        frac_dropped = 1.0 - jnp.mean(keep.astype(jnp.float32))
        frac_dropped = jax.lax.pmean(frac_dropped, ep)
        for a in dp:
            frac_dropped = jax.lax.pmean(frac_dropped, a)

        # reassemble the full local token block across EP ranks
        y_all = jax.lax.all_gather(y_my, ep, axis=0, tiled=True)  # [T_pad, D]
        y_all = y_all[:T_all]
        return y_all.reshape(Bl, S_, D_), aux, frac_dropped

    y, aux_loss, frac_dropped = dispatch(
        x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if m.num_shared_experts > 0:
        y = y + layers.apply_mlp(p["shared"], cfg, x)
    if m.dense_residual_d_ff > 0:
        y = y + layers.apply_mlp(p["dense"], cfg, x)
    return y, {"moe_aux_loss": aux_loss * m.aux_loss_weight,
               "moe_frac_dropped": frac_dropped}
