"""Data graphs for the benchmark, made from a configuration and ``--seed``.

A configuration's graph is drawn in two stages:

1. ``chung_lu_edges`` is a copy of the edge sampler of
   ``repro.graph.powerlaw_graph`` (Chung-Lu sampling, degree weights
   ``w_i ∝ i^{-1/(exponent-1)}``), kept here so that no change to the
   program changes the benchmark's data. Its draw gives the degree sequence.
2. ``bter_edges`` lays that degree sequence out as a BTER graph (Seshadhri,
   Kolda and Pinar, "Community structure and scale-free collections of
   Erdős-Rényi graphs", Phys. Rev. E 85, 2012): vertices of like degree form
   dense affinity blocks whose density sets the local clustering, and the
   degree the blocks leave is drawn Chung-Lu. A plain Chung-Lu graph has
   almost no clustering, and clustering decides how much work a clique
   query does.

The edge list is handed to the program's ``build_graph``. The graph's
structure comes from the configuration's ``graph_seed``; the run's ``--seed``
orders the vertices of equal degree (``relabel``). So every seed runs the
same work (the same degree sequence, ``d_pad`` and match counts, hence the
same compiled shapes), in another order.
"""
from __future__ import annotations

import numpy as np


def chung_lu_edges(num_vertices: int, avg_degree: float, exponent: float,
                   seed: int) -> np.ndarray:
    """Undirected edge list ``int64[m, 2]``, identical to the one
    ``repro.graph.powerlaw_graph`` builds its graph from."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, num_vertices + 1, dtype=np.float64)
    w = ranks ** (-1.0 / (exponent - 1.0))
    w /= w.sum()
    target_edges = int(num_vertices * avg_degree / 2)
    m = int(target_edges * 1.3) + 16
    src = rng.choice(num_vertices, size=m, p=w)
    dst = rng.choice(num_vertices, size=m, p=w)
    edges = np.stack([src, dst], axis=1)
    edges = edges[src != dst][:target_edges]
    perm = rng.permutation(num_vertices)
    return perm[edges]


def bter_edges(degrees: np.ndarray, clustering_max: float,
               clustering_decay: float, seed: int) -> np.ndarray:
    """Edge list ``int64[m, 2]`` of a BTER graph on ``len(degrees)`` vertices
    (duplicates and self loops left for ``csr``/``build_graph`` to drop).

    Vertices of degree 2 or more, lowest degree first, fill affinity blocks
    of ``d + 1`` vertices, ``d`` the block's lowest degree. A block is an
    Erdős-Rényi graph of density ``c_d ** (1/3)``, where the target local
    clustering of degree ``d`` is ``c_d = clustering_max *
    exp(-(d - 1) * clustering_decay)`` (the BTER paper's profile). Each
    vertex's degree beyond its block's expected share is then drawn
    Chung-Lu, with endpoints in proportion to that excess."""
    rng = np.random.default_rng(seed)
    d = np.asarray(degrees, np.int64)
    n = len(d)
    order = np.lexsort((rng.random(n), d))
    order = order[d[order] >= 2]
    starts, sizes = [], []
    i = 0
    while i < len(order):
        size = min(int(d[order[i]]) + 1, len(order) - i)
        starts.append(i)
        sizes.append(size)
        i += size
    starts, sizes = np.asarray(starts, np.int64), np.asarray(sizes, np.int64)
    lowest = d[order[starts]]
    density = (clustering_max * np.exp(-(lowest - 1) * clustering_decay)) ** (1 / 3)
    excess = d.astype(np.float64)
    pieces = []
    for size in np.unique(sizes):
        if size < 2:
            continue
        blocks = np.flatnonzero(sizes == size)
        members = order[starts[blocks][:, None] + np.arange(size)]
        iu, ju = np.triu_indices(size, 1)
        u, v = members[:, iu], members[:, ju]
        keep = rng.random(u.shape) < density[blocks][:, None]
        pieces.append(np.stack([u[keep], v[keep]], axis=1))
        excess[members] -= (density[blocks] * (size - 1))[:, None]
    excess = np.maximum(excess, 0.0)
    p = excess / excess.sum()
    m = int(round(excess.sum() / 2))
    pieces.append(np.stack([rng.choice(n, size=m, p=p),
                            rng.choice(n, size=m, p=p)], axis=1))
    return np.concatenate(pieces)


def relabel(edges: np.ndarray, num_vertices: int, seed: int) -> np.ndarray:
    """The same graph with its vertices numbered in order of degree, lowest
    first, and vertices of equal degree in an order drawn from ``seed``.

    Numbering by degree is the usual preparation of a graph for clique
    enumeration, and it fixes the work: the program's kernels cost by the
    largest degree in each tile of rows, so under a uniform random numbering
    one query's time on a TPU v5e moved by up to 7% from one numbering to
    another, with the same rows and matches."""
    deg = np.diff(csr(edges, num_vertices)[0])
    rng = np.random.default_rng(seed)
    order = np.lexsort((rng.random(num_vertices), deg))
    rank = np.empty(num_vertices, np.int64)
    rank[order] = np.arange(num_vertices)
    return rank[edges]


def structure_edges(config: dict) -> np.ndarray:
    """The configuration's graph before relabelling: the degree sequence of
    a Chung-Lu draw, laid out as a BTER graph."""
    n, seed = int(config["vertices"]), int(config["graph_seed"])
    offsets, _ = csr(chung_lu_edges(n, float(config["avg_degree"]),
                                    float(config["exponent"]), seed), n)
    return bter_edges(np.diff(offsets), float(config["clustering_max"]),
                      float(config["clustering_decay"]), seed)


def config_edges(config: dict, seed: int) -> np.ndarray:
    """The configuration's graph, numbered for the run's seed."""
    return relabel(structure_edges(config), int(config["vertices"]), seed)


def csr(edges: np.ndarray, num_vertices: int):
    """``(offsets, nbrs)`` of the simple undirected graph on ``edges``:
    self loops and duplicates dropped, rows sorted. The reference counts
    read this, never the program's own storage."""
    e = np.asarray(edges, np.int64).reshape(-1, 2)
    e = e[e[:, 0] != e[:, 1]]
    und = np.unique(np.sort(e, axis=1), axis=0)
    both = np.concatenate([und, und[:, ::-1]])
    both = both[np.lexsort((both[:, 1], both[:, 0]))]
    offsets = np.zeros(num_vertices + 1, np.int64)
    np.cumsum(np.bincount(both[:, 0], minlength=num_vertices), out=offsets[1:])
    return offsets, both[:, 1].copy()


def shape_stats(offsets: np.ndarray, nbrs: np.ndarray) -> dict:
    """The statistics SNAP publishes for its graphs, on this one: edges,
    average and largest degree, triangles, average local clustering (a
    vertex of degree under 2 counts as 0, as in SNAP's ``GetClustCf``) and
    the fraction of closed triangles (3 × triangles over connected
    triples)."""
    from bench.reference import triangles_per_vertex

    n = len(offsets) - 1
    deg = np.diff(offsets)
    tri = triangles_per_vertex(offsets, nbrs)
    triples = deg * (deg - 1) // 2
    local = np.where(deg >= 2, tri / np.maximum(triples, 1), 0.0)
    triangles = int(tri.sum() // 3)
    return {"vertices": n, "edges": int(len(nbrs) // 2),
            "avg_degree": float(len(nbrs) / n), "max_degree": int(deg.max()),
            "triangles": triangles, "triangles_per_vertex": triangles / n,
            "avg_clustering": float(local.mean()),
            "closed_triangle_fraction": 3 * triangles / int(triples.sum())}
