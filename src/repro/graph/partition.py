"""Random (hash) vertex partitioning — paper Section 2 "Graph Storage".

Vertex ``v`` is owned by shard ``v % num_shards`` (cyclic ≈ random for
arbitrary id assignment), stored with its full adjacency list, exactly like
the paper. For SPMD execution the per-shard padded adjacencies are stacked
into one array ``adj[P, V_per, D_pad]`` that a ``shard_map`` splits along the
leading axis, so every shard's local gather is a static-shape ``take``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.storage import Graph, INVALID


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PartitionedGraph:
    """Padded adjacency stacked by shard. Owner(v) = v % P, local(v) = v // P."""

    adj: np.ndarray  # int32[P, V_per, D_pad] (host; engines shard it)
    deg: np.ndarray  # int32[P, V_per]
    num_vertices: int
    num_shards: int

    @property
    def v_per_shard(self) -> int:
        return self.adj.shape[1]

    @property
    def d_pad(self) -> int:
        return self.adj.shape[2]

    def owner(self, vids: jax.Array) -> jax.Array:
        return jnp.where(vids >= 0, vids % self.num_shards, -1)

    def local_index(self, vids: jax.Array) -> jax.Array:
        return jnp.where(vids >= 0, vids // self.num_shards, 0)

    def shard_bytes(self) -> int:
        return int(self.adj.size * 4 + self.deg.size * 4) // self.num_shards

    def tree_flatten(self):
        return (self.adj, self.deg), (self.num_vertices, self.num_shards)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0], aux[1])


def partition_graph(graph: Graph, num_shards: int) -> PartitionedGraph:
    """Split ``graph`` into ``num_shards`` cyclic partitions (host-side)."""
    v = graph.num_vertices
    v_per = (v + num_shards - 1) // num_shards
    d_pad = graph.padded.d_pad

    adj = np.full((num_shards, v_per, d_pad), INVALID, dtype=np.int32)
    deg = np.zeros((num_shards, v_per), dtype=np.int32)

    full_adj = np.asarray(graph.padded.adj)
    full_deg = np.asarray(graph.padded.deg)
    vids = np.arange(v)
    owners = vids % num_shards
    locals_ = vids // num_shards
    adj[owners, locals_] = full_adj
    deg[owners, locals_] = full_deg

    return PartitionedGraph(
        adj=adj,
        deg=deg,
        num_vertices=v,
        num_shards=num_shards,
    )
