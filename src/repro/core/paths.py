"""Paper §6 "Applications": path queries on the HUGE machinery.

Shortest path and hop-constrained s-t simple-path enumeration are expressed
with the same PULL-EXTEND primitive (batched neighbour intersection/expansion
with injectivity filters) and bounded queues the enumeration engine uses:

  * ``shortest_path_length``: repeated PULL-EXTEND frontier expansion from the
    source (vectorised BFS over the padded adjacency) until the target joins
    the frontier.
  * ``hop_constrained_paths``: the paper's suggested bi-directional strategy —
    extend simple paths from both endpoints and PUSH-JOIN them in the middle
    on the meeting vertex (join key), verifying simplicity across the seam.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import operators as ops_mod
from repro.graph.storage import Graph, INVALID


def shortest_path_length(graph: Graph, source: int, target: int, max_hops: int = 64) -> Optional[int]:
    """Unweighted shortest path via vectorised frontier expansion."""
    v = graph.num_vertices
    dist = jnp.full((v,), jnp.iinfo(jnp.int32).max, jnp.int32).at[source].set(0)
    frontier = jnp.zeros((v,), bool).at[source].set(True)
    adj = jnp.asarray(graph.padded.adj)
    for hop in range(1, max_hops + 1):
        # neighbours of the whole frontier in one gather (PULL-EXTEND fetch)
        rows = jnp.where(frontier[:, None], adj, INVALID)
        nxt = jnp.zeros((v + 1,), bool).at[
            jnp.where(rows != INVALID, rows, v).reshape(-1)
        ].set(True)[:v]
        nxt = nxt & (dist == jnp.iinfo(jnp.int32).max)
        if not bool(jnp.any(nxt)):
            return None
        dist = jnp.where(nxt, hop, dist)
        if bool(dist[target] != jnp.iinfo(jnp.int32).max):
            return int(dist[target])
        frontier = nxt
    return None


def _extend_simple_paths(graph: Graph, paths: jnp.ndarray, n: int, cap: int):
    """One PULL-EXTEND over path tails with simplicity (injectivity) filters."""
    k = paths.shape[1]
    out, m = ops_mod.extend_batch(
        graph.padded.adj, paths, jnp.int32(n), ext=(k - 1,), lt=(), gt=(), out_cap=cap
    )
    return out, int(m)


def hop_constrained_paths(
    graph: Graph, source: int, target: int, hops: int, cap: int = 1 << 16
) -> List[Tuple[int, ...]]:
    """All simple s-t paths with exactly ``hops`` edges (bi-directional:
    extend ⌈h/2⌉ from s and ⌊h/2⌋ from t, join on the meeting vertex)."""
    fw_hops = (hops + 1) // 2
    bw_hops = hops - fw_hops

    def grow(start: int, steps: int):
        rows = jnp.full((cap, 1), INVALID, jnp.int32).at[0, 0].set(start)
        n = 1
        for _ in range(steps):
            rows, n = _extend_simple_paths(graph, rows, n, cap)
            if n == 0:
                return rows, 0
        return rows, n

    fw, nf = grow(source, fw_hops)     # [*, fw_hops+1] ending at the middle
    bw, nb = grow(target, bw_hops)     # [*, bw_hops+1] ending at the middle
    if nf == 0 or nb == 0:
        return []

    # PUSH-JOIN on the meeting vertex (last column of both sides).
    kf = fw.shape[1]
    kb = bw.shape[1]
    skeys, sbuf = ops_mod.join_prepare(fw, jnp.int32(nf), (kf - 1,))
    out, m, overflow = ops_mod.join_probe(
        skeys, sbuf, bw, jnp.int32(nb), (kb - 1,),
        tuple(range(kb - 1)),  # append the backward path minus the join vertex
        (), (), cap,
    )
    if bool(overflow):
        raise RuntimeError("path join overflow: raise cap")
    res = np.asarray(out[: int(m)])
    paths = []
    for row in res:
        fwd = [int(x) for x in row[:kf]]
        back = [int(x) for x in row[kf:]][::-1]
        full = fwd + back
        if len(set(full)) == len(full):  # simplicity across the seam
            paths.append(tuple(full))
    return paths
