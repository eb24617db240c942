"""Unit tests for the vectorised operator kernels."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import operators as ops
from repro.graph.storage import INVALID


def test_compact_packs_front():
    rows = jnp.asarray(np.arange(20).reshape(10, 2), jnp.int32)
    mask = jnp.asarray([True, False, True, False, True, False, False, True, False, False])
    out, n = ops.compact(rows, mask, 16)
    assert int(n) == 4
    np.testing.assert_array_equal(np.asarray(out[:4, 0]), [0, 4, 8, 14])
    assert np.all(np.asarray(out[4:]) == INVALID)


def test_queue_append_pop_roundtrip():
    buf = jnp.full((64, 3), INVALID, jnp.int32)
    rows = jnp.asarray(np.arange(30).reshape(10, 3), jnp.int32)
    buf, n = ops.queue_append(buf, jnp.int32(0), rows, jnp.int32(10))
    got, take, rem = ops.queue_pop(buf, n, 4)
    assert int(take) == 4 and int(rem) == 6
    np.testing.assert_array_equal(np.asarray(got[:4]), np.arange(18, 30).reshape(4, 3))


def test_row_membership_sorted():
    rows = jnp.asarray([[1, 3, 5, INVALID], [2, 4, 6, 8]], jnp.int32)
    queries = jnp.asarray([[3, 4, 1, INVALID], [8, 2, 5, 7]], jnp.int32)
    m = ops.row_membership(rows, queries)
    np.testing.assert_array_equal(
        np.asarray(m), [[True, False, True, False], [True, True, False, False]]
    )


def test_join_prepare_probe_vs_bruteforce():
    rng = np.random.default_rng(0)
    nl, nr = 200, 80
    lbuf = rng.integers(0, 12, size=(256, 3)).astype(np.int32)
    rbuf = rng.integers(0, 12, size=(128, 2)).astype(np.int32)
    key_left, key_right = (1,), (0,)
    skeys, sbuf = ops.join_prepare(jnp.asarray(lbuf), jnp.int32(nl), key_left)
    out, n, overflow = ops.join_probe(
        skeys, sbuf, jnp.asarray(rbuf), jnp.int32(nr),
        key_right, (1,), (), (), 1 << 14,
    )
    assert not bool(overflow)
    got = {tuple(map(int, r)) for r in np.asarray(out[: int(n)])}
    want = set()
    for i in range(nl):
        for j in range(nr):
            if lbuf[i, 1] == rbuf[j, 0]:
                want.add((int(lbuf[i, 0]), int(lbuf[i, 1]), int(lbuf[i, 2]), int(rbuf[j, 1])))
    assert got == want


def test_join_probe_leaves_rows_past_out_cap():
    """Rows whose matches do not fit stay unprobed, counted in ``left``: the
    probed rows are the last ones (the top of the stack they came from)."""
    lbuf = jnp.asarray([[k, k] for k in (1, 1, 1, 2, 2, 3)] + [[0, 0]] * 2, jnp.int32)
    skeys, sbuf = ops.join_prepare(lbuf, jnp.int32(6), (0,))
    rrows = jnp.asarray([[3, 30], [1, 10], [2, 20], [3, 31]], jnp.int32)
    # matches per right row: 1, 3, 2, 1 -> the last three fit in 4 slots
    out, n, left = ops.join_probe(skeys, sbuf, rrows, jnp.int32(4),
                                  (0,), (1,), (), (), 4)
    assert int(left) == 2 and int(n) == 3
    got = sorted(tuple(map(int, r)) for r in np.asarray(out[: int(n)]))
    assert got == [(2, 2, 20), (2, 2, 20), (3, 3, 31)]
    _, n, left = ops.join_probe(skeys, sbuf, rrows, jnp.int32(2),
                                (0,), (1,), (), (), 2)
    assert int(left) == 2 and int(n) == 0   # one row alone overflows


def test_prefix_sum_sat_matches_numpy():
    rng = np.random.default_rng(1)
    for n in (1, 127, 128, 129, 5000, 40000):
        x = rng.integers(0, 40, n).astype(np.int32)
        x[rng.integers(0, n, 2)] = 1 << 20
        for cap in (7, 1000, 1 << 19):
            want = np.minimum(np.cumsum(x.astype(np.int64)), cap)
            got = np.asarray(ops.prefix_sum_sat(jnp.asarray(x), cap))
            np.testing.assert_array_equal(got, want)


def test_join_probe_cross_filters():
    lbuf = jnp.asarray([[1, 5, 2], [3, 5, 4]], jnp.int32)
    rbuf = jnp.asarray([[5, 2], [5, 9]], jnp.int32)
    skeys, sbuf = ops.join_prepare(
        jnp.pad(lbuf, ((0, 6), (0, 0)), constant_values=0), jnp.int32(2), (1,)
    )
    out, n, _ = ops.join_probe(
        skeys, sbuf, jnp.pad(rbuf, ((0, 6), (0, 0)), constant_values=0), jnp.int32(2),
        (0,), (1,), ((2, 3),), (), 64,
    )  # cross_neq on (col2, col3): drops (…,2,…,2)
    got = {tuple(map(int, r)) for r in np.asarray(out[: int(n)])}
    assert (1, 5, 2, 2) not in got
    assert (1, 5, 2, 9) in got and (3, 5, 4, 2) in got


def test_lexsort_rows():
    cols = jnp.asarray([[2, 1], [1, 9], [2, 0], [1, 3]], jnp.int32)
    order = ops.lexsort_rows(cols)
    np.testing.assert_array_equal(np.asarray(order), [3, 1, 2, 0])


def test_scan_batch_filters():
    src = jnp.asarray([0, 0, 1, 1, 2, 2, 0, 0], jnp.int32)
    dst = jnp.asarray([1, 2, 0, 2, 0, 1, INVALID, INVALID], jnp.int32)
    rows, n = ops.scan_batch(src, dst, jnp.int32(0), jnp.int32(6), 8, (1,), ())
    # lt=(1,): keep src < dst only
    got = {tuple(map(int, r)) for r in np.asarray(rows[: int(n)])}
    assert got == {(0, 1), (0, 2), (1, 2)}


def test_partition_rows_by_key_groups_by_dest_shard():
    rows = jnp.asarray(
        [[0, 1], [5, 2], [3, 9], [7, 4], [2, 2], [9, 9]], jnp.int32
    )
    valid = jnp.asarray([True, True, True, True, False, True])
    send = ops.partition_rows_by_key(rows, valid, rows[:, 0], 4)
    assert send.shape == (4, 6, 2)
    got = {
        d: [tuple(map(int, r)) for r in np.asarray(send[d]) if r[0] != INVALID]
        for d in range(4)
    }
    assert got[0] == [(0, 1)]
    assert got[1] == [(5, 2), (9, 9)]      # 5 % 4 == 9 % 4 == 1
    assert got[2] == []                    # the only key%4==2 row was invalid
    assert got[3] == [(3, 9), (7, 4)]
    # every valid row lands exactly once, invalid rows are dropped
    assert sum(len(v) for v in got.values()) == 5
