"""The plain reference: match counts from the CSR, on the host.

A copy of ``chip_smoke.py``'s host counts (numpy and scipy, independent of
the engines): q1 the 4-cycle, q2 the diamond, q3 the 4-clique, each counted
once per instance. Nothing here imports the program.

``approximate_counts`` is the control of ``bench/control.py``: the same
counts computed the way an approximate counter would (edges kept with
probability ``p``, counts scaled by ``p^-|E_q|``, as DOULION does). Its
answers are not exact, so the check must refuse them.
"""
from __future__ import annotations

import numpy as np

QUERY_EDGES = {"q1": 4, "q2": 5, "q3": 6}


def _oriented(offsets: np.ndarray, nbrs: np.ndarray):
    """Edges oriented from lower to higher (degree, id) rank: CSR of the
    out-neighbours and the sorted keys ``u * n + v`` of every arc u→v."""
    n = len(offsets) - 1
    deg = np.diff(offsets)
    rank = np.empty(n, np.int64)
    rank[np.lexsort((np.arange(n), deg))] = np.arange(n)
    src = np.repeat(np.arange(n, dtype=np.int64), deg)
    dst = np.asarray(nbrs, np.int64)
    keep = rank[src] < rank[dst]
    s, d = src[keep], dst[keep]
    off = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(s, minlength=n), out=off[1:])
    return off, d, s * n + d


def _has_arc(keys: np.ndarray, a: np.ndarray, b: np.ndarray, n: int) -> np.ndarray:
    k = a * n + b
    i = np.minimum(np.searchsorted(keys, k), len(keys) - 1)
    return keys[i] == k


def _expand(off: np.ndarray, nb: np.ndarray, verts: np.ndarray, chunk: int = 1 << 22):
    """Yield ``(j, w)``: for every ``verts[j]``, each out-neighbour ``w``,
    in pieces of about ``chunk`` pairs."""
    cnt = off[verts + 1] - off[verts]
    ends = np.cumsum(cnt)
    lo = 0
    while lo < len(verts):
        hi = max(int(np.searchsorted(ends, (ends[lo - 1] if lo else 0) + chunk)), lo + 1)
        c = cnt[lo:hi]
        j = np.repeat(np.arange(lo, hi), c)
        first = np.repeat(np.cumsum(c) - c, c)
        w = nb[np.repeat(off[verts[lo:hi]], c) + np.arange(int(c.sum())) - first]
        yield j, w
        lo = hi


def host_counts(offsets: np.ndarray, nbrs: np.ndarray) -> dict:
    """Instance counts of q2 (diamond) and q3 (4-clique) from the CSR.

    Each triangle is listed once along its rank-oriented arcs a→b→c; a
    4-clique is a triangle plus a common out-neighbour of all three, and a
    diamond is a pair of triangles on one edge: sum over edges of C(t_e, 2).
    """
    n = len(offsets) - 1
    off, nb, keys = _oriented(np.asarray(offsets), np.asarray(nbrs))
    if len(keys) == 0:
        return {"q2": 0, "q3": 0}
    src = keys // n
    tri_per_arc = np.zeros(len(keys), np.int64)
    k4 = 0
    for j, w in _expand(off, nb, src):
        a, b = src[j], nb[j]
        hit = _has_arc(keys, b, w, n)
        a, b, c = a[hit], b[hit], w[hit]
        for u, v in ((a, b), (a, c), (b, c)):
            tri_per_arc += np.bincount(np.searchsorted(keys, u * n + v),
                                       minlength=len(keys))
        for t, x in _expand(off, nb, c):
            k4 += int(np.count_nonzero(
                _has_arc(keys, a[t], x, n) & _has_arc(keys, b[t], x, n)))
    diamonds = int(np.sum(tri_per_arc * (tri_per_arc - 1) // 2))
    return {"q2": diamonds, "q3": k4}


def triangles_per_vertex(offsets: np.ndarray, nbrs: np.ndarray) -> np.ndarray:
    """``int64[n]``: the triangles each vertex lies in, each triangle listed
    once along its rank-oriented arcs as in ``host_counts``."""
    n = len(offsets) - 1
    off, nb, keys = _oriented(np.asarray(offsets), np.asarray(nbrs))
    tri = np.zeros(n, np.int64)
    src = keys // n
    for j, w in _expand(off, nb, src):
        hit = _has_arc(keys, nb[j], w, n)
        for corner in (src[j][hit], nb[j][hit], w[hit]):
            tri += np.bincount(corner, minlength=n)
    return tri


def host_c4(offsets: np.ndarray, nbrs: np.ndarray) -> int:
    """Instances of q1 (4-cycle): sum over vertex pairs i != j of
    C(common neighbours, 2), over 4 (two diagonals, both orders)."""
    import scipy.sparse as sp

    n = len(offsets) - 1
    a = sp.csr_matrix(
        (np.ones(len(nbrs), np.int64), np.asarray(nbrs), np.asarray(offsets)),
        shape=(n, n),
    )
    w = (a @ a).tocoo()
    off_diag = w.row != w.col
    c = w.data[off_diag]
    return int(np.sum(c * (c - 1) // 2) // 4)


def counts(offsets: np.ndarray, nbrs: np.ndarray, queries) -> dict:
    """Exact counts of the named queries (any of q1, q2, q3)."""
    queries = set(queries)
    out = {}
    if queries & {"q2", "q3"}:
        out.update(host_counts(offsets, nbrs))
    if "q1" in queries:
        out["q1"] = host_c4(offsets, nbrs)
    return {q: out[q] for q in queries}


def approximate_counts(offsets: np.ndarray, nbrs: np.ndarray, queries,
                       seed: int, p: float = 0.5) -> dict:
    """The control: counts on an edge sample kept with probability ``p``,
    scaled by ``p^-|E_q|`` and rounded (an unbiased estimate, not a count)."""
    n = len(offsets) - 1
    src = np.repeat(np.arange(n, dtype=np.int64), np.diff(offsets))
    dst = np.asarray(nbrs, np.int64)
    lower = src < dst
    s, d = src[lower], dst[lower]
    keep = np.random.default_rng(seed).random(len(s)) < p
    s, d = s[keep], d[keep]
    both = np.concatenate([np.stack([s, d], 1), np.stack([d, s], 1)])
    both = both[np.lexsort((both[:, 1], both[:, 0]))]
    off = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(both[:, 0], minlength=n), out=off[1:])
    exact = counts(off, both[:, 1], queries)
    return {q: int(round(c / p ** QUERY_EDGES[q])) for q, c in exact.items()}
