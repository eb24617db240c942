"""Padded CSR graph storage.

Design notes (TPU adaptation)
-----------------------------
HUGE keeps each partition's adjacency in CSR and serves ``GetNbrs`` RPCs from
it. On TPU every access must be a dense gather, so alongside the classic CSR
pair ``(offsets, nbrs)`` we materialise a *padded adjacency matrix*
``adj[V, D_pad]`` whose rows are the sorted neighbour lists padded with the
sentinel ``INVALID`` (int32 max). Sorted rows + a monotone sentinel mean that

* set intersection (Eq. 2 of the paper) is a vectorised ``searchsorted``;
* padding never produces false positives (INVALID matches nothing);
* symmetry-breaking order filters are plain integer comparisons.

``D_pad`` is the max degree rounded up to a lane multiple (128) so Pallas
kernels can tile rows directly.

A :class:`Graph` holds host (numpy) arrays. Each engine places what it
needs where it needs it: the one-chip engine on its device, the
distributed engine straight into per-shard ``NamedSharding``s — so no
array is staged through device 0 on the way.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Sentinel for padded adjacency entries. Larger than any vertex id, so padded
# rows remain sorted and `searchsorted` membership tests are safe.
INVALID = np.int32(np.iinfo(np.int32).max)

_LANE = 128


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PaddedAdjacency:
    """Dense, padded adjacency: ``adj[v]`` = sorted neighbours of v, INVALID-padded."""

    adj: np.ndarray  # int32[V, D_pad]
    deg: np.ndarray  # int32[V]

    def __post_init__(self):
        # The Pallas fused kernels tile adjacency rows directly, so D_pad must
        # be a lane multiple — enforced here so a hand-built adjacency can't
        # silently violate what build_graph guarantees.
        if hasattr(self.adj, "ndim") and self.adj.ndim == 2:
            d_pad = self.adj.shape[1]
            if d_pad % _LANE != 0:
                raise ValueError(
                    f"PaddedAdjacency d_pad={d_pad} is not a multiple of the "
                    f"{_LANE}-lane tile (build_graph rounds up; do the same)"
                )

    @property
    def num_vertices(self) -> int:
        return self.adj.shape[0]

    @property
    def d_pad(self) -> int:
        return self.adj.shape[1]

    def neighbors(self, vids: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Gather padded neighbour rows for ``vids`` (INVALID rows for invalid ids)."""
        safe = jnp.clip(vids, 0, self.num_vertices - 1)
        rows = jnp.take(self.adj, safe, axis=0)
        degs = jnp.take(self.deg, safe, axis=0)
        ok = (vids >= 0) & (vids < self.num_vertices)
        rows = jnp.where(ok[..., None], rows, INVALID)
        degs = jnp.where(ok, degs, 0)
        return rows, degs

    def tree_flatten(self):
        return (self.adj, self.deg), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Graph:
    """An undirected data graph in CSR + padded form (host resident)."""

    offsets: np.ndarray  # int32[V+1]
    nbrs: np.ndarray  # int32[2E] sorted within each row
    padded: PaddedAdjacency

    @property
    def num_vertices(self) -> int:
        return self.offsets.shape[0] - 1

    @property
    def num_directed_edges(self) -> int:
        return self.nbrs.shape[0]

    @property
    def num_edges(self) -> int:
        return self.nbrs.shape[0] // 2

    @property
    def max_degree(self) -> int:
        return int(np.max(self.padded.deg))

    @property
    def avg_degree(self) -> float:
        return float(self.num_directed_edges) / max(1, self.num_vertices)

    def degree(self, vids: jax.Array) -> jax.Array:
        return jnp.take(self.padded.deg, jnp.clip(vids, 0, self.num_vertices - 1))

    def neighbors(self, vids: jax.Array) -> Tuple[jax.Array, jax.Array]:
        return self.padded.neighbors(vids)

    def has_edge(self, u: jax.Array, v: jax.Array) -> jax.Array:
        """Vectorised edge test via searchsorted on sorted padded rows.

        Broadcast-safe over scalar, 1-D, and batched inputs: ``vmap`` requires
        rank ≥ 1, so the padded rows and targets are flattened to one batch
        axis, searched, and reshaped back to the broadcast shape of ``u``/``v``.
        """
        u = jnp.asarray(u, jnp.int32)
        v = jnp.asarray(v, jnp.int32)
        rows, _ = self.padded.neighbors(u)
        batch_shape = jnp.broadcast_shapes(u.shape, v.shape)
        rows = jnp.broadcast_to(rows, batch_shape + rows.shape[-1:])
        vb = jnp.broadcast_to(v, batch_shape)
        flat_rows = rows.reshape(-1, rows.shape[-1])
        flat_v = vb.reshape(-1)
        idx = jax.vmap(jnp.searchsorted)(flat_rows, flat_v)
        idx = jnp.clip(idx, 0, flat_rows.shape[-1] - 1)
        found = jnp.take_along_axis(flat_rows, idx[:, None], axis=-1)[:, 0]
        return (found == flat_v).reshape(batch_shape)

    def size_bytes(self) -> int:
        return int(
            self.offsets.size * 4 + self.nbrs.size * 4 + self.padded.adj.size * 4 + self.padded.deg.size * 4
        )

    def tree_flatten(self):
        return (self.offsets, self.nbrs, self.padded), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def build_graph(edges: np.ndarray, num_vertices: int, d_pad: int | None = None) -> Graph:
    """Build a :class:`Graph` from an undirected edge array ``int[E, 2]``.

    Self loops and duplicate edges are removed; adjacency is symmetrised and
    sorted. ``d_pad`` defaults to max degree rounded up to 128 lanes.
    """
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    # Drop self loops, canonicalise, dedup.
    edges = edges[edges[:, 0] != edges[:, 1]]
    lo = np.minimum(edges[:, 0], edges[:, 1])
    hi = np.maximum(edges[:, 0], edges[:, 1])
    und = np.unique(np.stack([lo, hi], axis=1), axis=0)
    # Symmetrise.
    both = np.concatenate([und, und[:, ::-1]], axis=0)
    order = np.lexsort((both[:, 1], both[:, 0]))
    both = both[order]
    src, dst = both[:, 0], both[:, 1]

    deg = np.bincount(src, minlength=num_vertices).astype(np.int32)
    offsets = np.zeros(num_vertices + 1, dtype=np.int32)
    np.cumsum(deg, out=offsets[1:])
    nbrs = dst.astype(np.int32)

    max_deg = int(deg.max()) if deg.size else 0
    if d_pad is None:
        d_pad = max(_LANE, _round_up(max(1, max_deg), _LANE))
    else:
        # An explicit d_pad must still satisfy the module invariant (lane-
        # multiple rows: the Pallas kernels tile on it) — round up rather than
        # letting e.g. d_pad=3 pass validation and break kernels downstream.
        d_pad = max(_LANE, _round_up(int(d_pad), _LANE))
    if max_deg > d_pad:
        raise ValueError(f"d_pad={d_pad} smaller than max degree {max_deg}")

    adj = np.full((num_vertices, d_pad), INVALID, dtype=np.int32)
    # Row-fill padded adjacency (vectorised scatter).
    row_idx = src
    col_idx = (np.arange(both.shape[0]) - offsets[:-1].astype(np.int64)[src]).astype(np.int64)
    adj[row_idx, col_idx] = nbrs

    return Graph(
        offsets=offsets, nbrs=nbrs, padded=PaddedAdjacency(adj=adj, deg=deg)
    )


# ---------------------------------------------------------------------------
# Streaming updates (delta-plan substrate; DESIGN.md §Delta-plans)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GraphUpdateBatch:
    """A batch of graph mutations. Only edge inserts for now; ``kind`` keeps
    the wire format ready for deletes (delta flows would then also subtract
    matches, which needs old-epoch *adjacency* rather than the new-minus-delta
    reconstruction inserts allow)."""

    edges: np.ndarray  # int[E, 2] undirected; self loops / dups tolerated
    kind: str = "insert"

    def __post_init__(self):
        if self.kind != "insert":
            raise NotImplementedError(
                f"GraphUpdateBatch kind={self.kind!r}: only 'insert' is "
                "supported (deletes need old-epoch adjacency snapshots)"
            )

    @property
    def num_edges(self) -> int:
        return int(np.asarray(self.edges).reshape(-1, 2).shape[0])


@dataclasses.dataclass(frozen=True)
class AppliedUpdates:
    """Result of :func:`apply_updates`.

    ``graph`` is the post-batch graph G_new; ``delta`` is a :class:`Graph`
    over the *genuinely new* edges only (already-present edges and dups are
    dropped), which serves both as the delta scan source and as the
    old-epoch membership filter: for pure inserts,
    ``N_old(v) = N_new(v) \\ N_delta(v)``."""

    graph: Graph
    delta: Graph
    edges: np.ndarray       # int32[E_new, 2] canonical genuinely-new edges
    touched: np.ndarray     # int32[T] vertex ids whose adjacency rows changed

    @property
    def num_new_edges(self) -> int:
        return int(self.edges.shape[0])


def _canonical_new_edges(graph: Graph, batch: GraphUpdateBatch) -> np.ndarray:
    """Canonicalise a batch against the current graph: drop self loops,
    duplicates, out-of-range endpoints (an error), and edges already present."""
    edges = np.asarray(batch.edges, dtype=np.int64).reshape(-1, 2)
    if edges.size == 0:
        return np.zeros((0, 2), np.int64)
    v = graph.num_vertices
    if edges.min() < 0 or edges.max() >= v:
        raise ValueError(
            f"update batch references vertices outside [0, {v}) "
            "(vertex inserts are not supported; grow the graph by rebuild)"
        )
    edges = edges[edges[:, 0] != edges[:, 1]]
    lo = np.minimum(edges[:, 0], edges[:, 1])
    hi = np.maximum(edges[:, 0], edges[:, 1])
    und = np.unique(np.stack([lo, hi], axis=1), axis=0)
    if und.size == 0:
        return und.reshape(0, 2)
    # Drop edges already in the graph (host CSR membership per row).
    offsets = np.asarray(graph.offsets)
    nbrs = np.asarray(graph.nbrs)
    starts = offsets[und[:, 0]]
    ends = offsets[und[:, 0] + 1]
    present = np.zeros(und.shape[0], bool)
    for i, (a, b) in enumerate(und):
        row = nbrs[starts[i] : ends[i]]
        j = np.searchsorted(row, b)
        present[i] = j < row.shape[0] and row[j] == b
    return und[~present]


def apply_updates(graph: Graph, batch: GraphUpdateBatch) -> AppliedUpdates:
    """Apply an edge-insert batch, rebuilding only the affected rows.

    CSR: the new directed neighbours are spliced into ``nbrs`` with one
    vectorised ``np.insert`` (positions computed by per-row searchsorted) and
    offsets re-accumulated. Padded adjacency: only the touched rows are
    re-padded and scattered into a copy; when a touched row overflows
    ``d_pad``, the matrix grows by whole lane multiples (128) so the kernel
    tiling invariant survives the update."""
    new_edges = _canonical_new_edges(graph, batch)
    v = graph.num_vertices
    delta = build_graph(new_edges, v)
    if new_edges.shape[0] == 0:
        return AppliedUpdates(
            graph=graph, delta=delta,
            edges=new_edges.astype(np.int32),
            touched=np.zeros((0,), np.int32),
        )

    offsets = np.asarray(graph.offsets).astype(np.int64)
    nbrs = np.asarray(graph.nbrs)
    deg = np.asarray(graph.padded.deg).copy()

    # Directed view of the inserts, sorted by (row, value) so np.insert keeps
    # every row sorted even when one row receives several new neighbours.
    src = np.concatenate([new_edges[:, 0], new_edges[:, 1]])
    dst = np.concatenate([new_edges[:, 1], new_edges[:, 0]]).astype(np.int32)
    order = np.lexsort((dst, src))
    src, dst = src[order], dst[order]
    # Insert position of each new neighbour inside its row, relative to the
    # *original* flat nbrs array (np.insert semantics).
    pos = np.empty(src.shape[0], np.int64)
    for i in range(src.shape[0]):
        row = nbrs[offsets[src[i]] : offsets[src[i] + 1]]
        pos[i] = offsets[src[i]] + np.searchsorted(row, dst[i])
    new_nbrs = np.insert(nbrs, pos, dst)

    add_cnt = np.bincount(src, minlength=v).astype(np.int32)
    new_deg = deg + add_cnt
    new_offsets = np.zeros(v + 1, dtype=np.int32)
    np.cumsum(new_deg, out=new_offsets[1:])

    # Padded adjacency: grow columns by lane multiples if any row overflowed,
    # then rebuild only the touched rows from the fresh CSR.
    touched = np.unique(src).astype(np.int32)
    adj = np.asarray(graph.padded.adj)
    max_deg = int(new_deg.max())
    d_pad = adj.shape[1]
    if max_deg > d_pad:
        d_pad = _round_up(max_deg, _LANE)
        adj = np.pad(adj, ((0, 0), (0, d_pad - adj.shape[1])),
                     constant_values=INVALID)
    else:
        adj = adj.copy()
    for t in touched:
        row = new_nbrs[new_offsets[t] : new_offsets[t + 1]]
        adj[t, : row.shape[0]] = row
        adj[t, row.shape[0] :] = INVALID

    new_graph = Graph(
        offsets=new_offsets,
        nbrs=new_nbrs.astype(np.int32),
        padded=PaddedAdjacency(adj=adj, deg=new_deg.astype(np.int32)),
    )
    return AppliedUpdates(
        graph=new_graph, delta=delta,
        edges=new_edges.astype(np.int32), touched=touched,
    )


def from_edge_list(edge_list: Iterable[Sequence[int]], num_vertices: int | None = None) -> Graph:
    edges = np.asarray(list(edge_list), dtype=np.int64).reshape(-1, 2)
    if num_vertices is None:
        num_vertices = int(edges.max()) + 1 if edges.size else 0
    return build_graph(edges, num_vertices)


def to_networkx(graph: Graph):
    """Convert to networkx (host-side) for oracle validation."""
    import networkx as nx

    g = nx.Graph()
    g.add_nodes_from(range(graph.num_vertices))
    offsets = np.asarray(graph.offsets)
    nbrs = np.asarray(graph.nbrs)
    for v in range(graph.num_vertices):
        for u in nbrs[offsets[v] : offsets[v + 1]]:
            if v < u:
                g.add_edge(v, int(u))
    return g
