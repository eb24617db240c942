"""Mixture-of-Experts layer with HUGE push/pull-hybrid dispatch.

The paper's core physical-planning insight (Eq. 3 / Remark 3.1) applied to the
expert-parallel join between routed tokens and expert weights:

  push → shuffle the routed tokens onto the expert shards with an explicit
         ``all_to_all`` over the EP axis (the paper's pushing hash join:
         intermediate results keyed by expert id cross the network);
  pull → ``all_gather`` the expert weights onto the token shards and compute
         locally (the paper's PULL-EXTEND: fetch the operand data, which is
         bounded by the "graph" size — here 3·E·d·ff weights — independent of
         how many tokens are in flight).

Both modes compute identical values; only the collective schedule differs.
``core.hybrid_comm.moe_dispatch_mode`` picks the cheaper one per (arch ×
shape) at plan time, exactly like the paper's optimiser configures each join.

Experts are sharded ``[E, d, ff] = P("data", None, "model")`` (EP over the
data axis, TP over the model axis). Implementation is an explicit shard_map:
dispatch is sort-based (argsort by expert, capacity-bounded scatter), so no
GShard dense-dispatch einsum FLOPs.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.models.layers import dense_init
from repro.models.sharding import active_mesh, axis_size, batch_axes, pspec, shard


def moe_init(key, d_model: int, d_ff: int, num_experts: int, dtype) -> Dict:
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d_model, num_experts), jnp.float32),
        "w_gate": dense_init(ks[1], (num_experts, d_model, d_ff), dtype),
        "w_up": dense_init(ks[2], (num_experts, d_model, d_ff), dtype),
        "w_down": dense_init(ks[3], (num_experts, d_ff, d_model), dtype),
    }


def _route(xt, router, experts_per_token):
    """Top-k routing. Returns (gates [T,K] f32, idx [T,K] i32)."""
    logits = xt.astype(jnp.float32) @ router
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, experts_per_token)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx


def _positions_by_expert(idx_flat: jax.Array, num_experts: int):
    """Sort-based per-expert slot assignment: pos[i] = rank of i within its
    expert (stable in token order)."""
    n = idx_flat.shape[0]
    order = jnp.argsort(idx_flat, stable=True)
    sorted_e = jnp.take(idx_flat, order)
    start = jnp.searchsorted(sorted_e, jnp.arange(num_experts, dtype=idx_flat.dtype))
    rank = jnp.arange(n, dtype=jnp.int32) - jnp.take(start, sorted_e).astype(jnp.int32)
    pos = jnp.zeros((n,), jnp.int32).at[order].set(rank)
    return pos


def _expert_ffn(ex, wg, wu, wd, tp_axis: str | None):
    """ex [E_loc, C, d] @ per-expert FFN (ff possibly TP-sharded).

    (A forced-bf16-boundary variant was tried and REFUTED in §Perf qwen3
    iteration 1 — no wire saving, real precision cost — so compute follows
    the model dtype.)"""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ex, wg)) * jnp.einsum("ecd,edf->ecf", ex, wu)
    out = jnp.einsum("ecf,efd->ecd", h, wd)
    if tp_axis is not None:
        out = jax.lax.psum(out, tp_axis)
    return out


def _dispatch_local(xt, gates, idx, cap, num_experts):
    """Build [E, cap, d] buckets + bookkeeping for the combine."""
    t, k = idx.shape
    d = xt.shape[-1]
    idx_flat = idx.reshape(-1)
    pos = _positions_by_expert(idx_flat, num_experts)
    keep = pos < cap
    slot = jnp.where(keep, pos, cap)  # cap = OOB → dropped
    tok = jnp.broadcast_to(jnp.arange(t)[:, None], (t, k)).reshape(-1)
    buckets = jnp.zeros((num_experts, cap, d), xt.dtype).at[idx_flat, slot].set(
        jnp.take(xt, tok, axis=0), mode="drop"
    )
    return buckets, (idx_flat, slot, keep, tok)

def _combine_local(expert_out, gates, book, t):
    idx_flat, slot, keep, tok = book
    vals = expert_out[idx_flat, jnp.clip(slot, 0, expert_out.shape[1] - 1)]
    vals = vals * (gates.reshape(-1)[:, None] * keep[:, None]).astype(vals.dtype)
    d = expert_out.shape[-1]
    return jnp.zeros((t, d), vals.dtype).at[tok].add(vals)


def moe_block(
    params: Dict,
    x: jax.Array,                 # [B, S, D]
    *,
    experts_per_token: int,
    capacity_factor: float = 1.25,
    comm_mode: str = "auto",      # "push" | "pull" | "local"
) -> jax.Array:
    b, s, d = x.shape
    e = params["router"].shape[1]
    mesh = active_mesh()
    ep = axis_size("data") * axis_size("pod")
    if mesh is None or ep == 1 or comm_mode == "local":
        return _moe_local(params, x, experts_per_token, capacity_factor)
    if (b * s) % ep != 0:
        # Tokens cannot shard over the EP axis (e.g. batch-1 long-context
        # decode): tokens stay replicated, weights are pulled — exactly the
        # regime where Remark 3.1 says pulling wins anyway.
        return _moe_pull(params, x, experts_per_token, capacity_factor, mesh,
                         replicated_tokens=True)
    if comm_mode == "pull":
        return _moe_pull(params, x, experts_per_token, capacity_factor, mesh)
    return _moe_push(params, x, experts_per_token, capacity_factor, mesh)


# -- single-shard path (smoke tests / 1-device) ------------------------------

def _capacity(n_routed: int, e: int, capacity_factor: float) -> int:
    """Per-expert capacity. Small batches (decode, smoke tests) get lossless
    capacity so no token is ever dropped; large training batches use the
    standard capacity-factor bound."""
    if n_routed <= 8192:
        return n_routed
    return max(1, int(n_routed * capacity_factor / e) + 1)


def _moe_local(params, x, experts_per_token, capacity_factor):
    b, s, d = x.shape
    e = params["router"].shape[1]
    t = b * s
    xt = x.reshape(t, d)
    gates, idx = _route(xt, params["router"], experts_per_token)
    cap = _capacity(t * experts_per_token, e, capacity_factor)
    buckets, book = _dispatch_local(xt, gates, idx, cap, e)
    out = _expert_ffn(buckets, params["w_gate"], params["w_up"], params["w_down"], None)
    return _combine_local(out, gates, book, t).reshape(b, s, d)


# -- PUSH: all_to_all routed tokens over the EP axis --------------------------

def _ep_axes(e: int, mesh):
    """Largest suffix of (pod, data) whose size divides the expert count —
    experts shard over it; any dropped leading axis holds DP replicas."""
    axes = batch_axes()
    for i in range(len(axes) + 1):
        cand = axes[i:]
        size = 1
        for a in cand:
            size *= mesh.shape[a]
        if cand and e % size == 0:
            return cand, size
    return (), 1


def _moe_push(params, x, experts_per_token, capacity_factor, mesh):
    b, s, d = x.shape
    e = params["router"].shape[1]
    ep_axes, ep = _ep_axes(e, mesh)
    if not ep_axes:
        return _moe_pull(params, x, experts_per_token, capacity_factor, mesh)
    tp = "model" if "model" in mesh.axis_names else None
    e_loc = e // ep

    def f(xt, router, wg, wu, wd):
        # xt [T_loc, d]; wg [E_loc, d, ff_loc]
        t_loc = xt.shape[0]
        gates, idx = _route(xt, router, experts_per_token)
        n = t_loc * experts_per_token
        cap = _capacity(n, e, capacity_factor)
        idx_flat = idx.reshape(-1)
        pos = _positions_by_expert(idx_flat, e)
        keep = pos < cap
        slot = jnp.where(keep, pos, cap)
        tok = jnp.broadcast_to(
            jnp.arange(t_loc)[:, None], (t_loc, experts_per_token)
        ).reshape(-1)
        send = jnp.zeros((e, cap, d), xt.dtype).at[idx_flat, slot].set(
            jnp.take(xt, tok, axis=0), mode="drop"
        )
        # [E, cap, d] → [EP, E_loc, cap, d]; shuffle shard i's slice to expert
        # owner i (the pushing hash join). ep_axes is the (pod, data) product,
        # pod-major — matching the expert sharding order of the weights.
        send = send.reshape(ep, e_loc, cap, d)
        recv = jax.lax.all_to_all(send, ep_axes, split_axis=0, concat_axis=0, tiled=True)
        ex = jnp.swapaxes(recv.reshape(ep, e_loc, cap, d), 0, 1).reshape(e_loc, ep * cap, d)
        # TP psum deferred past the (linear) combine: reducing the [E, cap, d]
        # buckets costs cap·E/T ≈ topk·capacity_factor ≈ 10× more wire than
        # reducing the combined [T_loc, d] tokens (§Perf qwen3 iteration 2).
        out = _expert_ffn(ex, wg, wu, wd, None)
        back = jnp.swapaxes(out.reshape(e_loc, ep, cap, d), 0, 1).reshape(ep * e_loc, cap, d)
        got = jax.lax.all_to_all(back, ep_axes, split_axis=0, concat_axis=0, tiled=True)
        got = got.reshape(e, cap, d)
        combined = _combine_local(got, gates, (idx_flat, slot, keep, tok), t_loc)
        return jax.lax.psum(combined, tp) if tp else combined

    t = b * s
    xt = x.reshape(t, d)
    bspec = pspec("data")
    ep_spec = ep_axes if len(ep_axes) > 1 else ep_axes[0]
    tp_spec = pspec("model")[0]
    out = shard_map(
        f,
        mesh=mesh,
        in_specs=(
            P(bspec[0]), P(), P(ep_spec, None, tp_spec),
            P(ep_spec, None, tp_spec), P(ep_spec, tp_spec, None),
        ),
        out_specs=P(bspec[0]),
        check_vma=False,
    )(xt, params["router"], params["w_gate"], params["w_up"], params["w_down"])
    return out.reshape(b, s, d)


# -- PULL: all_gather expert weights over the EP axis --------------------------

def _moe_pull(params, x, experts_per_token, capacity_factor, mesh, replicated_tokens=False):
    b, s, d = x.shape
    e = params["router"].shape[1]
    ep_axes, ep = _ep_axes(e, mesh)
    tp = "model" if "model" in mesh.axis_names else None

    def f(xt, router, wg, wu, wd):
        t_loc = xt.shape[0]
        # Fetch stage (paper Alg. 4): pull the operand data once per batch —
        # bounded by the weight size (k·|E_G| of Remark 3.1), independent of
        # how many tokens are in flight.
        if ep_axes:
            wg = jax.lax.all_gather(wg, ep_axes, axis=0, tiled=True)
            wu = jax.lax.all_gather(wu, ep_axes, axis=0, tiled=True)
            wd = jax.lax.all_gather(wd, ep_axes, axis=0, tiled=True)
        gates, idx = _route(xt, router, experts_per_token)
        n = t_loc * experts_per_token
        cap = _capacity(n, e, capacity_factor)
        buckets, book = _dispatch_local(xt, gates, idx, cap, e)
        # psum deferred past the linear combine (see _moe_push).
        out = _expert_ffn(buckets, wg, wu, wd, None)
        combined = _combine_local(out, gates, book, t_loc)
        return jax.lax.psum(combined, tp) if tp else combined

    t = b * s
    xt = x.reshape(t, d)
    bspec = None if replicated_tokens else pspec("data")[0]
    ep_spec = (ep_axes if len(ep_axes) > 1 else ep_axes[0]) if ep_axes else None
    tp_spec = pspec("model")[0]
    out = shard_map(
        f,
        mesh=mesh,
        in_specs=(
            P(bspec), P(), P(ep_spec, None, tp_spec),
            P(ep_spec, None, tp_spec), P(ep_spec, tp_spec, None),
        ),
        out_specs=P(bspec),
        check_vma=False,
    )(xt, params["router"], params["w_gate"], params["w_up"], params["w_down"])
    return out.reshape(b, s, d)


def router_aux_loss(params: Dict, x: jax.Array, experts_per_token: int) -> jax.Array:
    """Switch-style load-balancing auxiliary loss."""
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    logits = xt.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, -1)
    e = probs.shape[-1]
    _, idx = jax.lax.top_k(probs, experts_per_token)
    frac_tokens = jnp.mean(jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(1), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    return e * jnp.sum(frac_tokens * frac_probs)
