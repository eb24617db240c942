"""Pallas TPU kernels for the enumeration hot path (paper Eq. 2, Alg. 3-4).

Three kernels implement the probe-fetch-intersect contract of DESIGN.md
§Fused-hot-path (the fused twin of the plain-jnp path in core/operators.py):

``multiway_membership_kernel``
    The bare Eq.-2 membership: for every partial match, test each candidate
    neighbour of the pivot against the (sorted, INVALID-padded) adjacency rows
    of all other extension vertices. The CPU implementation binary-searches;
    on TPU dynamic per-lane gathers are hostile to the VPU, so we *adapt*
    (per the brief, not port): membership is a **tiled compare-any** — a
    block of candidates is compared against each chunk of the other row at
    every lane rotation, reducing with ``|``, at full lane width.

``fused_extend_kernel`` / ``fused_verify_kernel``
    The full extend/verify hot path in one pass: per (row, extension-vertex)
    pair, gather the adjacency slab from one of *two* source tables — the
    LRBU value cache (single-device engine) or the fetched remote table
    (distributed engine) vs the local adjacency — select by the probe's
    hit mask, then run the Eq.-2 intersection plus injectivity and
    symmetry-break filters without materialising ``[B, E, D]`` slabs in HBM
    between stages. Slab addresses are scalar-prefetched into SMEM and each
    grid step DMAs its tile's slabs from the tables in HBM into VMEM; the
    probe's address computation is a tiny [B, E] scalar prologue that stays
    in jnp (see ops.py).

``lex_bounds_kernel``
    The PUSH-JOIN probe: equal-range bounds of each right-batch key in the
    sorted left side buffer. Binary search is again gather-hostile, so the
    bounds are computed as **tiled compare-count**: stream the sorted keys
    chunk-wise and count ``keys <lex q`` and ``keys ==lex q`` per query —
    ``lo = Σ lt``, ``cnt = Σ eq`` — which for a sorted table equals
    (searchsorted-left, equal-run length). O(CAP·B/lane) dense compares,
    zero gathers, accumulated across a 2-D grid.

All kernels run under ``interpret=True`` off-TPU so CPU CI executes the
kernel semantics (grid is scanned, not unrolled); pure-jnp reference twins
live in ref.py and dispatch in ops.py.

Shared layout: a grid step owns one tile of ``TILE_B`` rows (one sublane
tile). Membership of a candidate lane block ``[TILE_B, CHUNK]`` in a row is
a loop over the row's 128-lane chunks; within a chunk the block is compared
against the chunk at every lane rotation. Both loops stop
at the last non-INVALID chunk of the tile, so a tile of low-degree vertices
costs one chunk pair however wide ``D`` is padded, and only a hub pays
O(D²/CHUNK) — the loops are ``fori_loop``s, so compile time does not grow
with ``D``.
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.graph.storage import INVALID

TILE_B = 8   # rows per grid step: one sublane tile
CHUNK = 128  # lanes compared per step
_ROLLS_PER_STEP = 8  # lane rotations unrolled per loop step


def _valid_chunks(x: jax.Array) -> jax.Array:
    """Number of CHUNK-lane blocks of ``x[T, D]`` up to its last valid entry
    (rows need not be sorted: padding anywhere is skipped only at the end)."""
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    last = jnp.max(jnp.where(x != INVALID, lane + 1, 0))
    return (last + CHUNK - 1) // CHUNK


def _chunk(off) -> pl.Slice:
    return pl.ds(pl.multiple_of(off * CHUNK, CHUNK), CHUNK)


def _member(cand: jax.Array, load_row: Callable, n_chunks) -> jax.Array:
    """int32 0/1: each entry of ``cand[T, CHUNK]`` occurs in its row of the
    table that ``load_row(chunk_slice) -> [T, CHUNK]`` reads.

    Each chunk of the row is compared against the candidates at all CHUNK
    lane rotations (one lane roll per step), which pairs every candidate
    with every row entry of the same sublane."""

    def rotate(_, carry):
        acc, blk = carry
        for _ in range(_ROLLS_PER_STEP):
            acc = acc | (cand == blk).astype(jnp.int32)
            blk = pltpu.roll(blk, 1, 1)
        return acc, blk

    def body(oc, acc):
        acc, _ = lax.fori_loop(0, CHUNK // _ROLLS_PER_STEP, rotate,
                               (acc, load_row(_chunk(oc))))
        return acc

    return lax.fori_loop(0, n_chunks, body, jnp.zeros(cand.shape, jnp.int32))


def _intersect_tile(
    load_cand: Callable, loads_other: Sequence[Callable], n_other: Sequence,
    n_cand, mask_ref, keep: Callable,
) -> None:
    """mask_ref[T, D] = candidate present in every other row AND ``keep``.

    ``keep(cand[T, CHUNK]) -> bool`` holds the per-candidate filters;
    lanes past the candidates' last valid chunk stay 0."""
    mask_ref[...] = jnp.zeros(mask_ref.shape, jnp.int32)

    def body(cc, carry):
        cand = load_cand(_chunk(cc))
        acc = keep(cand).astype(jnp.int32)
        for load_row, n in zip(loads_other, n_other):
            acc = acc & _member(cand, load_row, n)
        mask_ref[:, _chunk(cc)] = acc
        return carry

    lax.fori_loop(0, n_cand, body, 0)


def _pad_rows(x: jax.Array, rows: int, fill) -> jax.Array:
    """Pad axis 0 of ``x`` up to ``rows`` with ``fill`` (no-op if long enough)."""
    n = rows - x.shape[0]
    if n <= 0:
        return x
    return jnp.concatenate([x, jnp.full((n,) + x.shape[1:], fill, x.dtype)], axis=0)


# ---------------------------------------------------------------------------
# Bare Eq.-2 membership
# ---------------------------------------------------------------------------


def _membership_body(cands_ref, others_ref, out_ref, *, n_other: int):
    n_cand = _valid_chunks(cands_ref[...])
    n_rows = [_valid_chunks(others_ref[e]) for e in range(n_other)]
    _intersect_tile(
        lambda sl: cands_ref[:, sl],
        [lambda sl, e=e: others_ref[e, :, sl] for e in range(n_other)],
        n_rows, n_cand, out_ref, lambda c: c != INVALID,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def multiway_membership_kernel(cands: jax.Array, others: jax.Array, *, interpret: bool = False) -> jax.Array:
    """cands[B, D] ∈ all of others[B, E, D]? (rows need not be sorted)."""
    b, d = cands.shape
    _, e, _ = others.shape
    assert b % TILE_B == 0, f"batch {b} must be a multiple of {TILE_B}"
    out = pl.pallas_call(
        functools.partial(_membership_body, n_other=e),
        grid=(b // TILE_B,),
        in_specs=[
            pl.BlockSpec((TILE_B, d), lambda i: (i, 0)),
            pl.BlockSpec((e, TILE_B, d), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_B, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, d), jnp.int32),
        interpret=interpret,
        name="multiway_membership_kernel",
    )(cands, jnp.transpose(others, (1, 0, 2)))
    return out != 0


# ---------------------------------------------------------------------------
# Fused extend/verify: probe-select slab gather → Eq.-2 intersection → filters
# ---------------------------------------------------------------------------
#
# Slab addressing contract (shared with ref.py / ops.py):
#   slab[b, e] = tab0[idx[0, b, e]]  if sel[b, e]
#              = tab1[idx[1, b, e]]  otherwise,
#   masked to INVALID where ~ok[b, e].
# ``tab0`` is the probe's primary source (LRBU value-cache slabs or the
# fetched remote table), ``tab1`` the fallback (local padded adjacency);
# both hold sorted, INVALID-padded rows of equal width D. Indices must be
# pre-clipped to the tables' row counts.
#
# The tables stay in HBM (``pl.ANY``). A table row is not a legal DMA
# source on its own — HBM arrays are tiled in (8, 128) blocks — so each
# slab is fetched as the aligned 8-row block that holds it, and its row is
# then picked out of VMEM.


def _gather_tiles(idx_ref, sel_ref, ok_ref, t0, t1, blocks, tile, sem, *,
                  n_ext: int, b: int):
    """DMA this grid step's TILE_B × n_ext slabs into ``tile[E, T, D]``."""
    i = pl.program_id(0)
    tables = (t1, t0)  # indexed by the select flag
    starts = []
    for r in range(TILE_B):
        for e in range(n_ext):
            k = (i * TILE_B + r) * n_ext + e
            s = sel_ref[k]
            for flag, tab in enumerate(tables):
                row = idx_ref[(1 - flag) * b * n_ext + k]
                start = jnp.minimum((row // 8) * 8, tab.shape[0] - 8)

                @pl.when(s == flag)
                def _copy(tab=tab, start=start, e=e, r=r):
                    pltpu.make_async_copy(
                        tab.at[pl.ds(pl.multiple_of(start, 8), 8)],
                        blocks.at[e, r], sem,
                    ).start()

            row = jnp.where(s == 1, idx_ref[k], idx_ref[b * n_ext + k])
            rows_in = jnp.where(s == 1, t0.shape[0], t1.shape[0])
            starts.append((k, e, r, row - jnp.minimum((row // 8) * 8, rows_in - 8)))
    for k, e, r, _ in starts:
        pltpu.make_async_copy(t1.at[pl.ds(0, 8)], blocks.at[e, r], sem).wait()
    for k, e, r, off in starts:
        slab = blocks[e, r, pl.ds(off, 1), :]
        tile[e, pl.ds(r, 1), :] = jnp.where(ok_ref[k] == 1, slab, INVALID)


def _fused_extend_kernel_body(
    idx_ref, sel_ref, ok_ref, rows_ref, t0, t1, cands_ref, mask_ref,
    blocks, tile, sem, *, n_ext: int, b: int, lt: Tuple[int, ...],
    gt: Tuple[int, ...],
):
    _gather_tiles(idx_ref, sel_ref, ok_ref, t0, t1, blocks, tile, sem,
                  n_ext=n_ext, b=b)
    rows = rows_ref[...]  # [T, K]

    def keep(cand):
        acc = cand != INVALID
        # Isomorphism (injectivity) check — Alg. 4 line 19.
        for col in range(rows.shape[1]):
            acc = acc & (cand != rows[:, col : col + 1])
        # Symmetry-breaking partial orders.
        for p in lt:
            acc = acc & (cand < rows[:, p : p + 1])
        for p in gt:
            acc = acc & (cand > rows[:, p : p + 1])
        return acc

    cands_ref[...] = tile[0]
    _intersect_tile(
        lambda sl: tile[0, :, sl],
        [lambda sl, e=e: tile[e, :, sl] for e in range(1, n_ext)],
        [_valid_chunks(tile[e]) for e in range(1, n_ext)],
        _valid_chunks(tile[0]), mask_ref, keep,
    )


def _slab_call(name, body, out_specs, out_shape, tab0, tab1, idx, sel, ok,
               rows, interpret: bool):
    """Shared pallas_call plumbing of the fused extend/verify kernels: batch
    padded to TILE_B, addressing scalars prefetched into SMEM, tables left in
    HBM for the body's aligned-block DMAs."""
    b, k = rows.shape
    e = idx.shape[2]
    d = tab0.shape[1]
    assert tab1.shape[1] == d, (tab0.shape, tab1.shape)
    bp = b + (-b) % TILE_B
    idx = jnp.stack([_pad_rows(idx[0], bp, 0), _pad_rows(idx[1], bp, 0)])
    scalars = (
        idx.reshape(-1).astype(jnp.int32),
        _pad_rows(sel, bp, 0).reshape(-1).astype(jnp.int32),
        _pad_rows(ok, bp, 0).reshape(-1).astype(jnp.int32),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bp // TILE_B,),
        in_specs=[
            pl.BlockSpec((TILE_B, k), lambda i, *_: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((e, TILE_B, 8, d), jnp.int32),
            pltpu.VMEM((e, TILE_B, d), jnp.int32),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    return pl.pallas_call(
        functools.partial(body, n_ext=e, b=bp),
        grid_spec=grid_spec,
        out_shape=out_shape(bp, d),
        interpret=interpret,
        name=name,
    )(*scalars, _pad_rows(rows, bp, INVALID),
      _pad_rows(tab0, 8, INVALID), _pad_rows(tab1, 8, INVALID))


@functools.partial(jax.jit, static_argnames=("lt", "gt", "interpret"))
def fused_extend_kernel(
    tab0: jax.Array,   # int32[R0, D] sorted INVALID-padded slabs (probe source)
    tab1: jax.Array,   # int32[R1, D] fallback slabs (local adjacency)
    idx: jax.Array,    # int32[2, B, E] pre-clipped row indices into tab0/tab1
    sel: jax.Array,    # int32[B, E] 1 → tab0, 0 → tab1
    ok: jax.Array,     # int32[B, E] 0 → slab forced to INVALID
    rows: jax.Array,   # int32[B, K] partial matches
    *,
    lt: Tuple[int, ...] = (),
    gt: Tuple[int, ...] = (),
    interpret: bool = False,
):
    """Fused PULL-EXTEND hot path. Returns (cands[B, D], mask[B, D]).

    ``cands`` is slab 0 (the pivot's adjacency); ``mask`` marks candidates
    present in every other slab that also pass injectivity and lt/gt orders.
    Row validity is NOT applied here — callers AND the batch's valid mask in.
    """
    b = rows.shape[0]
    tile_out = pl.BlockSpec((TILE_B, tab0.shape[1]), lambda i, *_: (i, 0))
    cands, mask = _slab_call(
        "fused_extend_kernel",
        functools.partial(_fused_extend_kernel_body, lt=lt, gt=gt),
        [tile_out, tile_out],
        lambda bp, d: [jax.ShapeDtypeStruct((bp, d), jnp.int32)] * 2,
        tab0, tab1, idx, sel, ok, rows, interpret,
    )
    return cands[:b], mask[:b] != 0


def _fused_verify_kernel_body(
    idx_ref, sel_ref, ok_ref, rows_ref, t0, t1, out_ref, blocks, tile, sem,
    *, n_ext: int, b: int, vpos: int,
):
    _gather_tiles(idx_ref, sel_ref, ok_ref, t0, t1, blocks, tile, sem,
                  n_ext=n_ext, b=b)
    target = rows_ref[...][:, vpos : vpos + 1]  # [T, 1]
    acc = (target != INVALID).astype(jnp.int32)
    for e in range(n_ext):
        def body(oc, hit, e=e):
            return hit | (tile[e, :, _chunk(oc)] == target).astype(jnp.int32)

        hit = lax.fori_loop(0, _valid_chunks(tile[e]), body,
                            jnp.zeros((TILE_B, CHUNK), jnp.int32))
        acc = acc & jnp.max(hit, axis=1, keepdims=True)
    out_ref[...] = jnp.broadcast_to(acc, out_ref.shape)


@functools.partial(jax.jit, static_argnames=("vpos", "interpret"))
def fused_verify_kernel(
    tab0: jax.Array,
    tab1: jax.Array,
    idx: jax.Array,
    sel: jax.Array,
    ok: jax.Array,
    rows: jax.Array,
    *,
    vpos: int,
    interpret: bool = False,
) -> jax.Array:
    """Fused VERIFY (§5.2 pulling-hash hint): keep rows whose ``rows[:, vpos]``
    is a member of every gathered slab. Returns bool[B] (row validity NOT
    applied — callers AND it in, same contract as fused_extend_kernel).
    Each row's verdict is written across one lane-dense CHUNK-wide output
    row; lane 0 is read back."""
    b = rows.shape[0]
    out = _slab_call(
        "fused_verify_kernel",
        functools.partial(_fused_verify_kernel_body, vpos=vpos),
        pl.BlockSpec((TILE_B, CHUNK), lambda i, *_: (i, 0)),
        lambda bp, d: jax.ShapeDtypeStruct((bp, CHUNK), jnp.int32),
        tab0, tab1, idx, sel, ok, rows, interpret,
    )
    return out[:b, 0] != 0


# ---------------------------------------------------------------------------
# PUSH-JOIN probe: equal-range bounds by tiled compare-count
# ---------------------------------------------------------------------------

BOUNDS_CHUNK = 128  # sorted-key rows per grid step


def _lex_bounds_kernel_body(keys_ref, q_ref, out_ref, *, kk: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    keys = keys_ref[...]  # [C, KK]
    q = q_ref[...]        # [T, KK]
    lt = jnp.zeros((q.shape[0], keys.shape[0]), jnp.bool_)
    eq = jnp.ones((q.shape[0], keys.shape[0]), jnp.bool_)
    for c in range(kk):
        a = keys[:, c][None, :]
        b = q[:, c][:, None]
        lt = lt | (eq & (a < b))
        eq = eq & (a == b)
    out_ref[:, 0] += jnp.sum(lt, axis=1, dtype=jnp.int32)
    out_ref[:, 1] += jnp.sum(eq, axis=1, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def lex_bounds_kernel(
    sorted_keys: jax.Array,  # int32[CAP, KK] lexicographically sorted, INVALID-padded
    queries: jax.Array,      # int32[B, KK]
    *,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Equal-range (lo, hi) of each query key in the sorted key table.

    For a sorted table, ``#(keys <lex q)`` is searchsorted-left and
    ``#(keys ==lex q)`` the run length, so the bounds come out of dense
    compare-count accumulation over a (query-tile × key-chunk) grid — no
    per-lane gathers. Queries equal to INVALID rows would miscount, so
    callers encode invalid queries as INVALID-1 (operators.join_probe does).
    """
    cap, kk = sorted_keys.shape
    b = queries.shape[0]
    pad_cap = (-cap) % BOUNDS_CHUNK
    if pad_cap:
        sorted_keys = jnp.concatenate(
            [sorted_keys, jnp.full((pad_cap, kk), INVALID, jnp.int32)], axis=0
        )
    pad_b = (-b) % TILE_B
    if pad_b:
        queries = jnp.concatenate(
            [queries, jnp.full((pad_b, kk), INVALID, jnp.int32)], axis=0
        )
    bp = b + pad_b
    out = pl.pallas_call(
        functools.partial(_lex_bounds_kernel_body, kk=kk),
        grid=((bp // TILE_B), (cap + pad_cap) // BOUNDS_CHUNK),
        in_specs=[
            pl.BlockSpec((BOUNDS_CHUNK, kk), lambda i, j: (j, 0)),
            pl.BlockSpec((TILE_B, kk), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_B, 2), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, 2), jnp.int32),
        interpret=interpret,
        name="lex_bounds_kernel",
    )(sorted_keys, queries)
    lo = out[:b, 0]
    return lo, lo + out[:b, 1]
