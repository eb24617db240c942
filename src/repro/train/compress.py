"""Int8 error-feedback gradient compression for the cross-pod DP exchange.

At 512+ chips the inter-pod (DCI) links are the slowest hop, so the cross-pod
gradient all-reduce dominates the collective roofline term. We compress it:
per-chunk int8 quantisation with error feedback (the quantisation residual is
added back into the next step's gradient, preserving convergence in
expectation). The reduce happens as reduce-scatter(int8) → local fp32 sum →
all-gather(int8): the bytes on the wire drop 2× vs bf16 / 4× vs fp32, and the
reduction math stays fp32.

Implemented as a shard_map over the ``pod`` axis so the int8 collectives are
explicit in the lowered HLO — the roofline harness measures the saving
directly (EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map


def _quant(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequant(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_psum_mean(flat: jax.Array, axis: str, mesh) -> jax.Array:
    """Mean-reduce a flat fp32 vector over ``axis`` with int8 wire format.

    flat must be reshapeable to [pods, chunk]: we pad to a multiple of the
    axis size, reduce-scatter in int8, sum locally in fp32, then all-gather
    the re-quantised partial sums.
    """
    n = mesh.shape[axis]

    def f(x):
        size = x.shape[0]
        pad = (-size) % (n * 128)
        xp = jnp.pad(x, (0, pad)).reshape(n, -1, 128)
        q, s = _quant(xp)                                   # int8 + f32 scale/row
        # reduce-scatter: a2a my n chunks, receive n partials of my chunk
        q_r = jax.lax.all_to_all(q, axis, split_axis=0, concat_axis=0, tiled=True)
        s_r = jax.lax.all_to_all(s, axis, split_axis=0, concat_axis=0, tiled=True)
        part = jnp.sum(_dequant(q_r, s_r).reshape(n, -1, 128), axis=0) / n
        q2, s2 = _quant(part)
        qg = jax.lax.all_gather(q2, axis, axis=0, tiled=False)
        sg = jax.lax.all_gather(s2, axis, axis=0, tiled=False)
        full = _dequant(qg, sg).reshape(-1)[:size + pad]
        return full[:size] if pad == 0 else full[:size]

    return shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)(flat)


def compress_gradients(grads, mesh, axis: str = "pod", error_state=None):
    """Apply compressed cross-pod mean to every gradient leaf, with error
    feedback. Returns (new_grads, new_error_state)."""
    if mesh is None or axis not in mesh.axis_names or mesh.shape[axis] == 1:
        return grads, error_state
    leaves, treedef = jax.tree.flatten(grads)
    err = jax.tree.leaves(error_state) if error_state is not None else [None] * len(leaves)
    new_leaves, new_err = [], []
    for g, ebuf in zip(leaves, err):
        gf = g.astype(jnp.float32)
        if ebuf is not None:
            gf = gf + ebuf
        flat = gf.reshape(-1)
        red = compressed_psum_mean(flat, axis, mesh).reshape(g.shape)
        new_err.append((gf - red).astype(jnp.bfloat16))  # residual feedback
        new_leaves.append(red.astype(g.dtype))
    return jax.tree.unflatten(treedef, new_leaves), jax.tree.unflatten(treedef, new_err)
