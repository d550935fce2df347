"""Explicit expert-parallel MoE execution (shard_map; DESIGN.md §4).

Two distributed layouts over the same routing math as models/moe.py:

* ``moe_ffn_tp`` — tokens stay data-sharded; expert weights are sharded
  over the "model" axis. Every TP shard routes the full (local-batch)
  token set, computes ONLY its resident experts' FFNs, and a psum over
  the model axis combines — each (token, choice) is handled by exactly
  one shard, so the sum is exact. No token movement, no weight gathers:
  this is the serving layout ``models/lm.py`` auto-selects when a
  sharding context is active.

* ``moe_ffn_ep`` — the classic all-to-all expert parallelism the
  models/moe.py docstring promises: tokens are sharded over the expert
  axis, each shard packs its tokens into per-destination-shard buffers,
  ``lax.all_to_all`` exchanges them, resident experts run, and a second
  all-to-all returns results for the gate-weighted combine.

Both return ``(out, router_logits, idx)`` exactly like ``moe_ffn`` and
fall back to it whenever no context is active or shapes do not divide,
so single-device tests run the dense path unchanged.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models.moe import capacity, group_tokens, moe_ffn, router_topk
from repro.models.layers import swiglu

from .ctx import current


def _shared_expert(p, x):
    if "shared_w1" not in p:
        return jnp.zeros_like(x)
    shared = swiglu(x, p["shared_w1"], p["shared_w3"], p["shared_w2"])
    sg = jax.nn.sigmoid(jnp.einsum("td,d->t", x, p["shared_gate"])
                        .astype(jnp.float32))
    return shared * sg[:, None].astype(x.dtype)


def _routed_weights(p):
    return p["router"], p["w1"], p["w3"], p["w2"]


# ---------------------------------------------------------------------------
# tensor-parallel experts (no token movement)
# ---------------------------------------------------------------------------

def _tp_body(router, w1, w3, w2, xs, *, tp_name: str, n_experts: int,
             top_k: int, cap_factor: float):
    t_loc, d = xs.shape
    logits = jnp.einsum("td,de->te", xs, router,
                        preferred_element_type=jnp.float32)
    gates, idx = router_topk(logits, top_k)

    e_loc = w1.shape[0]
    e0 = lax.axis_index(tp_name) * e_loc
    # non-resident choices route to a zero-weight drop bin (expert e_loc)
    idx_loc = jnp.where((idx >= e0) & (idx < e0 + e_loc), idx - e0, e_loc)
    cap = capacity(t_loc, top_k, n_experts, cap_factor)
    slot, keep, token_id, order = group_tokens(idx_loc, e_loc + 1, cap)

    buf = jnp.zeros(((e_loc + 1) * cap + 1, d), xs.dtype)
    tgt = jnp.where(keep, slot, (e_loc + 1) * cap)
    buf = buf.at[tgt].set(xs[token_id])
    xe = buf[:-1].reshape(e_loc + 1, cap, d)[:e_loc]

    g = jnp.einsum("ecd,edf->ecf", xe, w1)
    u = jnp.einsum("ecd,edf->ecf", xe, w3)
    ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, w2)
    # drop-bin slots read the appended zero rows -> contribute nothing
    ye = jnp.concatenate([ye, jnp.zeros((1, cap, d), ye.dtype)], axis=0)

    flat_gate = gates.reshape(-1)[order]
    y_tok = ye.reshape(-1, d)[jnp.where(keep, slot, 0)]
    contrib = jnp.where(keep[:, None], y_tok, 0) \
        * flat_gate[:, None].astype(xs.dtype)
    out = jnp.zeros((t_loc, d), xs.dtype).at[token_id].add(contrib)
    return lax.psum(out, tp_name), logits, idx


def moe_ffn_tp(p, x: jax.Array, *, n_experts: int, top_k: int,
               cap_factor: float = 1.25):
    """shard_map TP-MoE. x: (T, d) tokens. Same contract as moe_ffn."""
    ctx = current()
    if ctx is None:
        return moe_ffn(p, x, n_experts=n_experts, top_k=top_k,
                       cap_factor=cap_factor)
    sizes = ctx.axis_sizes()
    tp, tp_size = ctx.tp_axis, sizes.get(ctx.tp_axis, 1)
    dp_prod = ctx.logical_sizes()["dp"]
    t, _ = x.shape
    if tp not in sizes or n_experts % tp_size or t % dp_prod:
        return moe_ffn(p, x, n_experts=n_experts, top_k=top_k,
                       cap_factor=cap_factor)

    dpe = ctx.dp_axes[0] if len(ctx.dp_axes) == 1 else ctx.dp_axes
    tok = P(dpe if ctx.dp_axes else None, None)
    body = functools.partial(_tp_body, tp_name=tp, n_experts=n_experts,
                             top_k=top_k, cap_factor=cap_factor)
    out, logits, idx = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(None, None), P(tp, None, None), P(tp, None, None),
                  P(tp, None, None), tok),
        out_specs=(tok, tok, P(tok[0], None)),
        check_vma=False,
    )(*_routed_weights(p), x)
    return out + _shared_expert(p, x), logits, idx


# ---------------------------------------------------------------------------
# all-to-all expert parallelism
# ---------------------------------------------------------------------------

def _ep_body(router, w1, w3, w2, xs, *, ep_name: str, n_shards: int,
             n_experts: int, top_k: int, cap_factor: float):
    t_loc, d = xs.shape
    e_loc = n_experts // n_shards
    logits = jnp.einsum("td,de->te", xs, router,
                        preferred_element_type=jnp.float32)
    gates, idx = router_topk(logits, top_k)

    # --- pack per destination shard ------------------------------------
    dest = idx // e_loc                              # (T_loc, K)
    c_send = capacity(t_loc, top_k, n_shards, cap_factor)
    slot, keep, token_id, order = group_tokens(dest, n_shards, c_send)
    n_slots = n_shards * c_send
    tgt = jnp.where(keep, slot, n_slots)
    send_x = jnp.zeros((n_slots + 1, d), xs.dtype).at[tgt].set(xs[token_id])
    e_flat = idx.reshape(-1)[order]
    send_e = jnp.full((n_slots + 1,), -1, jnp.int32).at[tgt].set(e_flat)

    # --- exchange tokens ------------------------------------------------
    recv_x = lax.all_to_all(send_x[:-1].reshape(n_shards, c_send, d),
                            ep_name, 0, 0).reshape(n_slots, d)
    recv_e = lax.all_to_all(send_e[:-1].reshape(n_shards, c_send),
                            ep_name, 0, 0).reshape(n_slots)

    # --- resident expert compute ---------------------------------------
    e0 = lax.axis_index(ep_name) * e_loc
    el = jnp.where(recv_e >= 0, recv_e - e0, e_loc)  # invalid -> drop bin
    c_loc = capacity(n_slots, 1, max(e_loc, 1), cap_factor)
    slot2, keep2, tid2, _ = group_tokens(el[:, None], e_loc + 1, c_loc)
    buf = jnp.zeros(((e_loc + 1) * c_loc + 1, d), xs.dtype)
    tgt2 = jnp.where(keep2, slot2, (e_loc + 1) * c_loc)
    buf = buf.at[tgt2].set(recv_x[tid2])
    xe = buf[:-1].reshape(e_loc + 1, c_loc, d)[:e_loc]
    g = jnp.einsum("ecd,edf->ecf", xe, w1)
    u = jnp.einsum("ecd,edf->ecf", xe, w3)
    ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, w2)
    ye = jnp.concatenate([ye, jnp.zeros((1, c_loc, d), ye.dtype)], axis=0)
    y_tok = ye.reshape(-1, d)[jnp.where(keep2, slot2, 0)]
    y_flat = jnp.zeros((n_slots, d), xs.dtype).at[tid2].add(
        jnp.where(keep2[:, None], y_tok, 0))

    # --- return results and combine at the source ----------------------
    y_back = lax.all_to_all(y_flat.reshape(n_shards, c_send, d),
                            ep_name, 0, 0).reshape(n_slots, d)
    flat_gate = gates.reshape(-1)[order]
    contrib = jnp.where(keep[:, None], y_back[jnp.where(keep, slot, 0)], 0) \
        * flat_gate[:, None].astype(xs.dtype)
    out = jnp.zeros((t_loc, d), xs.dtype).at[token_id].add(contrib)
    return out, logits, idx


def moe_ffn_ep(p, x: jax.Array, *, n_experts: int, top_k: int,
               cap_factor: float = 1.25):
    """All-to-all EP MoE: tokens AND experts sharded over the "model"
    axis (tokens additionally over the data axes). Same contract as
    moe_ffn; falls back to it off-mesh or when shapes do not divide."""
    ctx = current()
    if ctx is None:
        return moe_ffn(p, x, n_experts=n_experts, top_k=top_k,
                       cap_factor=cap_factor)
    sizes = ctx.axis_sizes()
    ep, n_shards = ctx.tp_axis, sizes.get(ctx.tp_axis, 1)
    dp_prod = ctx.logical_sizes()["dp"]
    t, _ = x.shape
    if (ep not in sizes or n_experts % n_shards
            or t % (dp_prod * n_shards)):
        return moe_ffn(p, x, n_experts=n_experts, top_k=top_k,
                       cap_factor=cap_factor)

    tok_axes: Tuple[str, ...] = tuple(ctx.dp_axes) + (ep,)
    tok = P(tok_axes if len(tok_axes) > 1 else tok_axes[0], None)
    body = functools.partial(_ep_body, ep_name=ep, n_shards=n_shards,
                             n_experts=n_experts, top_k=top_k,
                             cap_factor=cap_factor)
    out, logits, idx = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(None, None), P(ep, None, None), P(ep, None, None),
                  P(ep, None, None), tok),
        out_specs=(tok, tok, P(tok[0], None)),
        check_vma=False,
    )(*_routed_weights(p), x)
    return out + _shared_expert(p, x), logits, idx
