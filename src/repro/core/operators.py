"""Vectorised JAX implementations of the primitive operators (paper §4.2-4.4).

Batches of partial matches are dense int32 arrays ``rows[B, K]`` with a valid
count ``n`` (rows ≥ n are ignored; INVALID-filled). Queues are fixed-capacity
stacks ``(buf[CAP, K], n)`` — enumeration has set semantics so LIFO order is
irrelevant, and stack pops are cheap dynamic slices.

All functions are pure and jit-compiled with static shape arguments; the
BFS/DFS-adaptive scheduler (scheduler.py) drives them batch-by-batch exactly
as Algorithm 5 prescribes.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.graph.storage import INVALID


# ---------------------------------------------------------------------------
# Small utilities
# ---------------------------------------------------------------------------

def row_membership(sorted_rows: jax.Array, queries: jax.Array) -> jax.Array:
    """queries[b, j] ∈ sorted_rows[b, :] (rows sorted ascending, INVALID-padded)."""
    idx = jax.vmap(jnp.searchsorted)(sorted_rows, queries)
    idx = jnp.clip(idx, 0, sorted_rows.shape[-1] - 1)
    found = jnp.take_along_axis(sorted_rows, idx, axis=-1)
    return (found == queries) & (queries != INVALID)


_SCAN_BLOCK = 128


def row_prefix(x: jax.Array) -> jax.Array:
    """Inclusive prefix sums along the last axis of a non-negative integer
    array, as int32, by one product with a triangular matrix on the MXU.
    Exact while each row's total stays below 2^24 (the products run at full
    f32 precision); meant for short axes, up to a few hundred entries."""
    w = x.shape[-1]
    tri = (jnp.arange(w)[:, None] <= jnp.arange(w)[None, :]).astype(jnp.float32)
    return jnp.dot(x.astype(jnp.float32), tri,
                   precision=lax.Precision.HIGHEST).astype(jnp.int32)


def prefix_count(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum of a non-negative int vector whose total stays
    below 2^24 (a 0/1 mask of any length under that), as int32.

    Blocked: each 128-entry block goes through ``row_prefix`` and the block
    totals recurse. It compiles in well under a second at any length, where
    the TPU compiler takes seconds per shape on a long ``jnp.cumsum``."""
    n = x.shape[0]
    assert n < 1 << 24, f"prefix_count over {n} entries would lose exactness"
    if n <= _SCAN_BLOCK:
        return row_prefix(x)
    within = row_prefix(
        jnp.pad(x, (0, (-n) % _SCAN_BLOCK)).reshape(-1, _SCAN_BLOCK))
    totals = within[:, -1]
    before = prefix_count(totals) - totals
    return (within + before[:, None]).reshape(-1)[:n]


def prefix_sum_sat(x: jax.Array, cap: int) -> jax.Array:
    """Inclusive prefix sum of a non-negative int32 vector, saturated at
    ``cap`` (exact wherever the true sum is below ``cap``; ``cap`` itself
    elsewhere), with ``128·cap < 2^31``.

    Blocked like ``prefix_count``, but in int32 shifted adds within each
    128-entry block: ``lax.associative_scan`` over 10^6 entries costs the
    TPU compiler minutes."""
    assert _SCAN_BLOCK * cap < 1 << 31, cap
    n = x.shape[0]
    x = jnp.minimum(x, cap)
    s = jnp.pad(x, (0, (-n) % _SCAN_BLOCK)).reshape(-1, _SCAN_BLOCK)
    k = 1
    while k < _SCAN_BLOCK:
        s = s + jnp.pad(s[:, :-k], ((0, 0), (k, 0)))
        k *= 2
    if s.shape[0] > 1:
        before = jnp.pad(prefix_sum_sat(s[:, -1], cap)[:-1], (1, 0))
        s = s + before[:, None]
    return jnp.minimum(s, cap).reshape(-1)[:n]


def compact(rows: jax.Array, mask: jax.Array, out_cap: int) -> Tuple[jax.Array, jax.Array]:
    """Pack masked rows to the front of a fresh [out_cap, K] buffer.

    The rows move by a gather: a 1-D scatter writes each kept row's source
    index into its output slot, then whole rows are taken. (Scattering the
    rows themselves is equivalent, but the TPU compiler spends seconds on
    every 2-D row scatter, once per shape; ``prefix_count`` avoids the same
    cost in ``jnp.cumsum``.)"""
    n_in = rows.shape[0]
    pos = prefix_count(mask) - 1
    n = jnp.sum(mask, dtype=jnp.int32)
    tgt = jnp.where(mask, pos, out_cap)  # out-of-range → dropped by scatter
    src = jnp.full((out_cap,), n_in, jnp.int32).at[tgt].set(
        jnp.arange(n_in, dtype=jnp.int32), mode="drop"
    )
    out = jnp.take(rows, jnp.minimum(src, n_in - 1), axis=0)
    return jnp.where((src < n_in)[:, None], out, INVALID), n


def compact_extend(rows: jax.Array, cands: jax.Array, mask: jax.Array,
                   j0: jax.Array, out_cap: int) -> Tuple[jax.Array, jax.Array]:
    """Rows ``j0 … j0+out_cap`` of the extension ``(rows[r], cands[r, c])``
    over every kept ``mask[r, c]``, in row-major order, as a fresh
    ``[out_cap, K+1]`` buffer (INVALID past the end), with the true count.

    No index array spans ``[B, D]``: that work is dense (the masked block
    sums), and every scatter or gather runs over ``B`` or ``out_cap``
    indices. Slot ``j`` finds its row by the row offsets' prefix (each row
    marks its first slot, a prefix count over the slots turns the marks
    into row numbers), its rank ``t`` within the row, then its 128-lane
    block of the row by the blocks' prefix and its lane by a prefix inside
    that one gathered block."""
    b, k = rows.shape
    lanes = _SCAN_BLOCK
    pad = (-cands.shape[1]) % lanes
    nb = (cands.shape[1] + pad) // lanes
    assert b * nb * lanes < 1 << 24, "offsets would lose exactness"
    kept = jnp.pad(jnp.where(mask, cands, INVALID), ((0, 0), (0, pad)),
                   constant_values=INVALID).reshape(b * nb, lanes)
    per_block = jnp.sum(kept != INVALID, axis=1, dtype=jnp.int32).reshape(b, nb)
    block_start = row_prefix(per_block) - per_block               # [B, nb]
    count = jnp.sum(per_block, axis=1, dtype=jnp.int32)
    start = prefix_count(count) - count                            # [B]
    n = start[-1] + count[-1]

    marks = jnp.zeros((out_cap,), jnp.int32).at[
        jnp.where(start >= j0, start - j0, out_cap)].add(1, mode="drop")
    r = jnp.sum(start < j0, dtype=jnp.int32) + prefix_count(marks) - 1
    r = jnp.clip(r, 0, b - 1)
    head = jnp.take(jnp.concatenate([rows, start[:, None], block_start], 1),
                    r, axis=0)                                     # [W, K+1+nb]
    j = j0 + jnp.arange(out_cap, dtype=jnp.int32)
    t = j - head[:, k]
    firsts = head[:, k + 1:]
    blk = jnp.sum(firsts[:, 1:] <= t[:, None], axis=1, dtype=jnp.int32)
    t = t - jnp.sum(jnp.where(jnp.arange(nb) == blk[:, None], firsts, 0),
                    axis=1, dtype=jnp.int32)
    got = jnp.take(kept, r * nb + blk, axis=0)                    # [W, 128]
    lane = jnp.sum(row_prefix(got != INVALID) <= t[:, None], axis=1,
                   dtype=jnp.int32)
    cand = jnp.sum(jnp.where(jnp.arange(lanes) == lane[:, None], got, 0),
                   axis=1, dtype=jnp.int32)
    out = jnp.concatenate([head[:, :k], cand[:, None]], axis=1)
    return jnp.where((j < n)[:, None], out, INVALID), n


@jax.jit
def dedup_pad(vids: jax.Array) -> jax.Array:
    """Unique valid vertex ids packed to the front, INVALID-padded to the input
    length (the merged-RPC dedup; also the precondition of the LRBU value-cache
    insert, whose scatters would race on duplicate keys)."""
    n = vids.shape[0]
    v = jnp.where((vids >= 0) & (vids != INVALID), vids, INVALID)
    s = jnp.sort(v)
    keep = (s != INVALID) & jnp.concatenate(
        [jnp.ones((1,), bool), s[1:] != s[:-1]]
    )
    pos = jnp.cumsum(keep) - 1
    tgt = jnp.where(keep, pos, n)
    return jnp.full((n,), INVALID, jnp.int32).at[tgt].set(s, mode="drop")


def lexsort_rows(cols: jax.Array) -> jax.Array:
    """Stable lexicographic argsort by columns of ``cols[N, C]`` (col 0 primary)."""
    n = cols.shape[0]
    order = jnp.arange(n, dtype=jnp.int32)
    for c in range(cols.shape[1] - 1, -1, -1):
        vals = jnp.take(cols[:, c], order)
        perm = jnp.argsort(vals, stable=True)
        order = jnp.take(order, perm)
    return order


# ---------------------------------------------------------------------------
# Queue (fixed-capacity stack)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, donate_argnums=(0,))
def queue_append(buf: jax.Array, n: jax.Array, rows: jax.Array, m: jax.Array):
    """Write ``rows`` (valid prefix of ``m``) at the stack top ``n``.

    One contiguous write of all ``R`` rows: rows past ``m`` land in free
    space and are never read. Requires ``n + R <= CAP`` — the Lemma-5.2
    slack the scheduler reserves before it runs a producer (a write that
    did not fit would be shifted down over live rows)."""
    cap = buf.shape[0]
    buf = lax.dynamic_update_slice(buf, rows, (n, jnp.int32(0)))
    return buf, jnp.minimum(n + m, cap)


@functools.partial(jax.jit, static_argnames=("batch",))
def queue_pop(buf: jax.Array, n: jax.Array, batch: int):
    take = jnp.minimum(n, batch).astype(jnp.int32)
    start = jnp.maximum(n - take, 0)
    rows = lax.dynamic_slice(buf, (start, jnp.int32(0)), (batch, buf.shape[1]))
    return rows, take, n - take


@functools.partial(jax.jit, static_argnames=("num_shards",))
def partition_rows_by_key(rows: jax.Array, valid: jax.Array, key: jax.Array,
                          num_shards: int) -> jax.Array:
    """Group rows by destination shard ``key % num_shards`` for an all_to_all.

    Returns ``send[P, B, K]`` (INVALID-padded): ``send[d]`` holds the rows
    destined to shard ``d``, packed to the front in input order. This is the
    send tensor of the PUSH-JOIN hash shuffle (DESIGN.md §Shuffle-join) — the
    collective itself lives in distributed.py; this part is pure and
    unit-testable. One ``compact`` per destination: no sort, which the TPU
    compiler takes seconds over at the shuffle's batch sizes.
    """
    b = rows.shape[0]
    dest = jnp.where(valid, key % num_shards, num_shards)
    return jnp.stack(
        [compact(rows, dest == d, b)[0] for d in range(num_shards)]
    )


# ---------------------------------------------------------------------------
# SCAN
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("batch", "lt", "gt"))
def scan_batch(src: jax.Array, dst: jax.Array, cursor: jax.Array, total: jax.Array,
               batch: int, lt: Tuple[int, ...], gt: Tuple[int, ...]):
    """Emit one batch of directed-edge matches [batch, 2] starting at cursor.

    ``src``/``dst`` must be padded to a multiple of ``batch`` (engine does
    this) so the dynamic slice never clamps; ``total`` is the true edge count.
    """
    s = lax.dynamic_slice(src, (cursor,), (batch,))
    d = lax.dynamic_slice(dst, (cursor,), (batch,))
    valid = (cursor + jnp.arange(batch)) < total
    rows = jnp.stack([s, d], axis=1)
    mask = valid
    for p in lt:  # col0 < col(p): only p=1 arises for scans
        mask = mask & (rows[:, 0] < rows[:, p])
    for p in gt:
        mask = mask & (rows[:, 0] > rows[:, p])
    rows = jnp.where(mask[:, None], rows, INVALID)
    out, n = compact(rows, mask, batch)
    return out, n


# ---------------------------------------------------------------------------
# PULL-EXTEND — intersect stage (Eq. 2). The fetch stage lives in cache.py /
# distributed.py; on a single device all adjacency is local.
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("ext", "lt", "gt", "out_cap", "use_kernel"),
)
def extend_batch(
    adj: jax.Array,            # int32[V, D] padded sorted adjacency
    rows: jax.Array,           # int32[B, K]
    n: jax.Array,
    ext: Tuple[int, ...],
    lt: Tuple[int, ...],
    gt: Tuple[int, ...],
    out_cap: int,
    use_kernel: bool = True,
    j0: jax.Array | int = 0,
):
    """Extend each row by the common neighbours of its ``ext`` columns.

    Returns rows ``j0 … j0+out_cap`` of the step's output (``compact_extend``)
    and its true count, which may exceed ``out_cap``: a caller resumes at
    ``j0 + out_cap`` with the same compiled shape.

    Eq.-2 membership goes through ``kernels/intersect`` (the native kernel
    on the TPU, where the XLA binary search is gather-bound; its jnp twin
    elsewhere). ``use_kernel=False`` is the kernel-free path a failed
    kernel degrades to."""
    b, k = rows.shape
    v = adj.shape[0]
    valid_row = jnp.arange(b) < n

    def nbr_rows(col):
        vids = rows[:, col]
        safe = jnp.clip(vids, 0, v - 1)
        r = jnp.take(adj, safe, axis=0)
        ok = (vids >= 0) & (vids < v)
        return jnp.where(ok[:, None], r, INVALID)

    cands = nbr_rows(ext[0])  # [B, D]
    mask = (cands != INVALID) & valid_row[:, None]
    if len(ext) > 1:
        if use_kernel:
            from repro.kernels.intersect import ops as ik

            others = jnp.stack([nbr_rows(d) for d in ext[1:]], axis=1)  # [B, E-1, D]
            mask = mask & ik.multiway_membership(cands, others)
        else:
            for d in ext[1:]:
                mask = mask & row_membership(nbr_rows(d), cands)
    # Isomorphism (injectivity) check — Alg. 4 line 19.
    for col in range(k):
        mask = mask & (cands != rows[:, col : col + 1])
    # Symmetry-breaking partial orders.
    for p in lt:
        mask = mask & (cands < jnp.where(valid_row, rows[:, p], -1)[:, None])
    for p in gt:
        mask = mask & (cands > jnp.where(valid_row, rows[:, p], INVALID)[:, None])
    return compact_extend(rows, cands, mask, j0, out_cap)


@functools.partial(jax.jit, static_argnames=("ext", "verify_pos", "out_cap"))
def verify_batch(
    adj: jax.Array,
    rows: jax.Array,
    n: jax.Array,
    ext: Tuple[int, ...],
    verify_pos: int,
    out_cap: int,
):
    """Pulling-hash 'hint' (§5.2): keep rows whose f(root) ∈ ∩ N(f(ext))."""
    b, k = rows.shape
    v = adj.shape[0]
    valid_row = jnp.arange(b) < n
    target = rows[:, verify_pos : verify_pos + 1]  # [B, 1]
    mask = valid_row
    for d in ext:
        vids = rows[:, d]
        safe = jnp.clip(vids, 0, v - 1)
        r = jnp.take(adj, safe, axis=0)
        ok = (vids >= 0) & (vids < v)
        r = jnp.where(ok[:, None], r, INVALID)
        mask = mask & row_membership(r, target)[:, 0]
    return compact(rows, mask, out_cap)


# ---------------------------------------------------------------------------
# Delta epochs (DESIGN.md §Delta-plans). For insert-only batches the old
# adjacency is reconstructed as membership-in-new AND NOT membership-in-delta,
# so no pre-batch snapshot is kept; ``old`` is a static bool per intersected
# position. Candidate gathers always read the *new* padded adjacency — an
# old-epoch position only adds a delta-membership veto, keeping the Eq.-2
# structure (and its cost bound) intact.
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("ext", "old", "lt", "gt", "out_cap"),
)
def delta_extend_batch(
    adj: jax.Array,        # int32[V, D]  post-batch padded sorted adjacency
    delta_adj: jax.Array,  # int32[V, Dd] padded sorted adjacency of new edges
    rows: jax.Array,       # int32[B, K]
    n: jax.Array,
    ext: Tuple[int, ...],
    old: Tuple[bool, ...],  # aligned with ext; True → old-epoch edge
    lt: Tuple[int, ...],
    gt: Tuple[int, ...],
    out_cap: int,
):
    b, k = rows.shape
    v = adj.shape[0]
    valid_row = jnp.arange(b) < n

    def nbr_rows(table, col):
        vids = rows[:, col]
        safe = jnp.clip(vids, 0, v - 1)
        r = jnp.take(table, safe, axis=0)
        ok = (vids >= 0) & (vids < v)
        return jnp.where(ok[:, None], r, INVALID)

    cands = nbr_rows(adj, ext[0])  # [B, D]
    mask = (cands != INVALID) & valid_row[:, None]
    if old[0]:
        mask = mask & ~row_membership(nbr_rows(delta_adj, ext[0]), cands)
    for d, is_old in zip(ext[1:], old[1:]):
        mask = mask & row_membership(nbr_rows(adj, d), cands)
        if is_old:
            mask = mask & ~row_membership(nbr_rows(delta_adj, d), cands)
    for col in range(k):
        mask = mask & (cands != rows[:, col : col + 1])
    for p in lt:
        mask = mask & (cands < jnp.where(valid_row, rows[:, p], -1)[:, None])
    for p in gt:
        mask = mask & (cands > jnp.where(valid_row, rows[:, p], INVALID)[:, None])

    d = cands.shape[1]
    expanded = jnp.concatenate(
        [
            jnp.broadcast_to(rows[:, None, :], (b, d, k)),
            cands[:, :, None],
        ],
        axis=2,
    ).reshape(b * d, k + 1)
    return compact(expanded, mask.reshape(b * d), out_cap)


@functools.partial(
    jax.jit, static_argnames=("ext", "old", "verify_pos", "out_cap")
)
def delta_verify_batch(
    adj: jax.Array,
    delta_adj: jax.Array,
    rows: jax.Array,
    n: jax.Array,
    ext: Tuple[int, ...],
    old: Tuple[bool, ...],
    verify_pos: int,
    out_cap: int,
):
    """Epoch-aware VERIFY: f(root) ∈ ∩ N_ep(f(ext)) with per-position epochs."""
    b, k = rows.shape
    v = adj.shape[0]
    valid_row = jnp.arange(b) < n
    target = rows[:, verify_pos : verify_pos + 1]  # [B, 1]
    mask = valid_row

    def nbr_rows(table, col):
        vids = rows[:, col]
        safe = jnp.clip(vids, 0, v - 1)
        r = jnp.take(table, safe, axis=0)
        ok = (vids >= 0) & (vids < v)
        return jnp.where(ok[:, None], r, INVALID)

    for d, is_old in zip(ext, old):
        mask = mask & row_membership(nbr_rows(adj, d), target)[:, 0]
        if is_old:
            mask = mask & ~row_membership(nbr_rows(delta_adj, d), target)[:, 0]
    return compact(rows, mask, out_cap)


# ---------------------------------------------------------------------------
# Fused hot path (DESIGN.md §Fused-hot-path): the cache-probe / fetch-table
# addressing is computed by the engines as a tiny [B, E] prologue; slab
# movement, Eq.-2 intersection, injectivity and symmetry-order filters run in
# one kernel pass (or its ref twin). Expansion and compaction stay out here —
# they are scatter-shaped and gain nothing from fusion.
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("lt", "gt", "out_cap", "force_kernel")
)
def fused_extend_batch(
    tab0: jax.Array,   # int32[R0, D] probe source (cache slabs / fetched table)
    tab1: jax.Array,   # int32[R1, D] fallback (local padded adjacency)
    idx: jax.Array,    # int32[2, B, E]
    sel: jax.Array,    # int32[B, E]
    ok: jax.Array,     # int32[B, E]
    rows: jax.Array,   # int32[B, K]
    n: jax.Array,
    lt: Tuple[int, ...],
    gt: Tuple[int, ...],
    out_cap: int,
    force_kernel: bool = False,
):
    from repro.kernels.intersect import ops as ik

    b, k = rows.shape
    valid_row = jnp.arange(b) < n
    cands, mask = ik.fused_extend(
        tab0, tab1, idx, sel, ok, rows, lt=lt, gt=gt, force_kernel=force_kernel
    )
    mask = mask & valid_row[:, None]
    d = cands.shape[1]
    expanded = jnp.concatenate(
        [
            jnp.broadcast_to(rows[:, None, :], (b, d, k)),
            cands[:, :, None],
        ],
        axis=2,
    ).reshape(b * d, k + 1)
    return compact(expanded, mask.reshape(b * d), out_cap)


@functools.partial(jax.jit, static_argnames=("vpos", "out_cap", "force_kernel"))
def fused_verify_batch(
    tab0: jax.Array,
    tab1: jax.Array,
    idx: jax.Array,
    sel: jax.Array,
    ok: jax.Array,
    rows: jax.Array,
    n: jax.Array,
    vpos: int,
    out_cap: int,
    force_kernel: bool = False,
):
    from repro.kernels.intersect import ops as ik

    b = rows.shape[0]
    valid_row = jnp.arange(b) < n
    keep = ik.fused_verify(
        tab0, tab1, idx, sel, ok, rows, vpos=vpos, force_kernel=force_kernel
    )
    return compact(rows, keep & valid_row, out_cap)


# ---------------------------------------------------------------------------
# PUSH-JOIN — buffered distributed hash join (§4.3). The left side is sorted
# by key once (the paper's external merge sort of the buffered branch); right
# batches then probe it with a vectorised lexicographic binary search and the
# per-key cross products are emitted. This mirrors the paper's "read back the
# data of each join key in a streaming manner" with O(log) probes.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("key_cols",))
def join_prepare(lbuf: jax.Array, ln: jax.Array, key_cols: Tuple[int, ...]):
    """Sort the fully-buffered left side by its join key (invalid rows last)."""
    nl = lbuf.shape[0]
    valid = jnp.arange(nl) < ln
    keys = jnp.where(valid[:, None], lbuf[:, list(key_cols)], INVALID)
    order = lexsort_rows(keys)
    return jnp.take(keys, order, axis=0), jnp.take(lbuf, order, axis=0)


# Lexicographic equal-range search lives with the kernels now: the binary-
# search twin (used here by default) in kernels/intersect/ref.py, the Pallas
# compare-count kernel in kernels/intersect/intersect.py. Re-exported under
# the old names for callers/tests that import them from operators.
from repro.kernels.intersect.ref import _lex_cmp, lex_bounds_ref as _lex_bounds  # noqa: E402


@functools.partial(
    jax.jit,
    static_argnames=(
        "key_right", "right_extra", "cross_neq", "cross_lt", "out_cap",
        "use_kernel", "force_kernel",
    ),
)
def join_probe(
    sorted_keys: jax.Array,   # [CAP, kk] left keys, sorted, INVALID-padded
    sorted_buf: jax.Array,    # [CAP, KL] left rows in the same order
    rrows: jax.Array,         # [B, KR]
    rn: jax.Array,
    key_right: Tuple[int, ...],
    right_extra: Tuple[int, ...],
    cross_neq: Tuple[Tuple[int, int], ...],
    cross_lt: Tuple[Tuple[int, int], ...],
    out_cap: int,
    use_kernel: bool = False,
    force_kernel: bool = False,
):
    """Probe the first ``rn`` right rows against the sorted left side.

    Emits the matches of the *last* right rows whose matches fit in
    ``out_cap`` together — the top of the stack the rows were popped from —
    and returns ``(out, n_out, left)``: ``left`` right rows, the first ones,
    were not probed. A caller that can push them back resumes from there;
    one that cannot treats ``left > 0`` as an overflow."""
    b, kr = rrows.shape
    rvalid = jnp.arange(b) < rn
    rkeys = jnp.where(rvalid[:, None], rrows[:, list(key_right)], INVALID - 1)
    if use_kernel:
        from repro.kernels.intersect import ops as ik

        lo, hi = ik.lex_bounds(sorted_keys, rkeys, force_kernel=force_kernel)
    else:
        lo, hi = _lex_bounds(sorted_keys, rkeys)
    cnt = jnp.where(rvalid, hi - lo, 0)
    # Matches of rows i.. (saturating: only "fits in out_cap" matters).
    after = prefix_sum_sat(cnt[::-1], out_cap + 1)[::-1]
    take = rvalid & (after <= out_cap)
    cnt = jnp.where(take, cnt, 0)
    off = prefix_sum_sat(cnt, out_cap + 1) - cnt
    total = jnp.sum(cnt)
    left = jnp.sum(rvalid & ~take, dtype=jnp.int32)

    o = jnp.arange(out_cap, dtype=jnp.int32)
    g = jnp.searchsorted(off + cnt, o, side="right").astype(jnp.int32)
    g = jnp.clip(g, 0, b - 1)
    li = o - jnp.take(off, g)
    lpos = jnp.clip(jnp.take(lo, g) + li, 0, sorted_buf.shape[0] - 1)
    valid = o < total

    lrows_out = jnp.take(sorted_buf, lpos, axis=0)
    rrows_out = jnp.take(rrows, g, axis=0)
    out = (
        jnp.concatenate([lrows_out, rrows_out[:, list(right_extra)]], axis=1)
        if right_extra
        else lrows_out
    )
    for a, bcol in cross_neq:
        valid = valid & (out[:, a] != out[:, bcol])
    for a, bcol in cross_lt:
        valid = valid & (out[:, a] < out[:, bcol])
    out = jnp.where(valid[:, None], out, INVALID)
    out2, nout = compact(out, valid, out_cap)
    return out2, nout, left


# ---------------------------------------------------------------------------
# Legacy single-shot group join (kept for the distributed engine's shuffle path
# and property tests).
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("key_left", "key_right", "right_extra", "cross_neq", "cross_lt", "out_cap"),
)
def join_batch(
    lbuf: jax.Array,  # [NL, KL]
    ln: jax.Array,
    rbuf: jax.Array,  # [NR, KR]
    rn: jax.Array,
    key_left: Tuple[int, ...],
    key_right: Tuple[int, ...],
    right_extra: Tuple[int, ...],
    cross_neq: Tuple[Tuple[int, int], ...],
    cross_lt: Tuple[Tuple[int, int], ...],
    out_cap: int,
):
    nl, kl = lbuf.shape
    nr, kr = rbuf.shape
    nn = nl + nr
    kk = len(key_left)

    lvalid = jnp.arange(nl) < ln
    rvalid = jnp.arange(nr) < rn
    lkeys = jnp.where(lvalid[:, None], lbuf[:, list(key_left)], INVALID)
    rkeys = jnp.where(rvalid[:, None], rbuf[:, list(key_right)], INVALID)

    keys = jnp.concatenate([lkeys, rkeys], axis=0)                     # [N, kk]
    side = jnp.concatenate(
        [jnp.zeros(nl, jnp.int32), jnp.ones(nr, jnp.int32)], axis=0
    )
    orig = jnp.concatenate(
        [jnp.arange(nl, dtype=jnp.int32), jnp.arange(nr, dtype=jnp.int32)], axis=0
    )

    order = lexsort_rows(jnp.concatenate([keys, side[:, None]], axis=1))
    sk = jnp.take(keys, order, axis=0)
    ss = jnp.take(side, order)
    so = jnp.take(orig, order)

    newgrp = jnp.concatenate(
        [jnp.ones((1,), bool), jnp.any(sk[1:] != sk[:-1], axis=1)], axis=0
    )
    gid = jnp.cumsum(newgrp.astype(jnp.int32)) - 1                     # [N]
    gstart = jax.ops.segment_min(jnp.arange(nn, dtype=jnp.int32), gid, num_segments=nn)
    lcnt = jax.ops.segment_sum((ss == 0).astype(jnp.int32), gid, num_segments=nn)
    rcnt = jax.ops.segment_sum((ss == 1).astype(jnp.int32), gid, num_segments=nn)
    # Groups keyed by INVALID (out-of-count rows) contribute nothing.
    gkey0 = jnp.full((nn,), INVALID, dtype=jnp.int32).at[gid].min(sk[:, 0])
    pairs = jnp.where(gkey0 == INVALID, 0, lcnt * rcnt)
    out_off = jnp.cumsum(pairs) - pairs                                # exclusive
    total = jnp.sum(pairs)

    o = jnp.arange(out_cap, dtype=jnp.int32)
    g = jnp.searchsorted(out_off + pairs, o, side="right").astype(jnp.int32)
    g = jnp.clip(g, 0, nn - 1)
    local = o - jnp.take(out_off, g)
    rc = jnp.maximum(jnp.take(rcnt, g), 1)
    li = local // rc
    ri = local % rc
    gs = jnp.take(gstart, g)
    lpos = jnp.clip(gs + li, 0, nn - 1)
    rpos = jnp.clip(gs + jnp.take(lcnt, g) + ri, 0, nn - 1)
    lorig = jnp.take(so, lpos)
    rorig = jnp.take(so, rpos)
    valid = o < total

    lrows = jnp.take(lbuf, jnp.clip(lorig, 0, nl - 1), axis=0)
    rrows = jnp.take(rbuf, jnp.clip(rorig, 0, nr - 1), axis=0)
    out = jnp.concatenate([lrows, rrows[:, list(right_extra)]], axis=1) if right_extra else lrows
    for a, bcol in cross_neq:
        valid = valid & (out[:, a] != out[:, bcol])
    for a, bcol in cross_lt:
        valid = valid & (out[:, a] < out[:, bcol])
    out = jnp.where(valid[:, None], out, INVALID)
    out2, nout = compact(out, valid, out_cap)
    overflow = total > out_cap
    return out2, nout, overflow
