"""Distributed HUGE engine: shard_map SPMD execution of arbitrary plan DAGs.

This is the real-collective counterpart of engine.py: the graph is hash-
partitioned over the mesh axis ``shards`` (paper §2), partial matches live on
their producing shard, and every operator of the translated dataflow — SCAN,
PULL-EXTEND, VERIFY, PUSH-JOIN, SINK — executes with actual communication:

  fetch stage     dedup the batch's remote vertices (merged-RPC aggregation),
                  route requests to their owners with an ``all_to_all``,
                  gather CSR rows, return them with a second ``all_to_all``
                  — the GetNbrs RPC as a dense collective;
  intersect stage read-only: Eq. 2 membership over local partition + the
                  fetched table (zero-copy in the paper's sense: pure gather);
  shuffle join    PUSH-JOIN hash-partitions *both* inputs by join key with an
                  ``all_to_all`` (the paper's shuffle of R(q'_l), R(q'_r));
                  matching keys co-locate, so the probe itself is local —
                  DESIGN.md §Shuffle-join;
  stealing        each extend batch's results are re-spread evenly with one
                  more ``all_to_all`` (proactive inter-machine work stealing,
                  §5.3 — see DESIGN.md §SPMD-work-stealing on why SPMD makes
                  stealing deterministic).

Scope: any optimiser plan — scan → {extend, verify, join} DAGs, driven by the
generalised BFS/DFS-adaptive scheduler (scheduler.py) over the dataflow's
topological order. PUSH-JOIN is a barrier operator: it shuffle-buffers either
input whenever rows are available, but only probes once every ancestor of its
buffered (left) branch has drained.

Memory bound: every queue — operator output queues *and* join side buffers —
is a preallocated [P, CAP, K] device array, so the paper's Theorem 5.4 bound
stays structural (a compile-time constant, not a runtime promise).
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.analysis.flowcheck import verify_flow
from repro.core import operators as ops_mod
from repro.core.faults import (
    EnumerationFault,
    FaultPlan,
    QueuePressure,
    ShardLoss,
)
from repro.core.dataflow import Dataflow, OpDesc, merge_flows, translate
from repro.core.optimizer import optimal_plan
from repro.core.cost import GraphStats
from repro.core.plan import ExecutionPlan
from repro.core.query import QueryGraph
from repro.core.scheduler import AdaptiveScheduler
from repro.graph.partition import partition_graph
from repro.graph.storage import Graph, INVALID


_log = logging.getLogger("repro.distributed")


@dataclasses.dataclass
class DistConfig:
    batch_size: int = 256
    queue_capacity: int = 1 << 16
    join_buffer_capacity: int = 1 << 17  # rows per join side buffer per shard
    join_out_capacity: int = 1 << 17     # most rows one probe step emits
    axis: str = "shards"
    rebalance: bool = True               # inter-machine work stealing
    fused: bool = False                  # fused extend/verify + probe kernels
    force_kernel: bool = False           # interpret-mode kernels on CPU (CI)
    faults: Optional[FaultPlan] = None   # deterministic fault injection
    recover: bool = True                 # restart-based recovery: SPMD
    #   execution is deterministic, so a recoverable fault rebuilds the
    #   runtimes and re-runs the flow (halved batch on QueuePressure)
    max_retries: int = 3                 # recovery attempts per _execute
    min_batch_size: int = 32             # degradation floor for batch halving


class _DQueue:
    """A distributed device queue: ``buf[P, cap+slack, K]`` + counts ``n[P]``.

    ``capacity`` is the logical fill level the scheduler gates on; ``slack``
    absorbs one worst-case batch beyond it (Lemma 5.2 overflow allowance).
    The host caches ``max(n)`` so scheduling decisions don't re-sync."""

    def __init__(self, eng: "DistributedEngine", width: int, slack: int,
                 capacity: Optional[int] = None):
        cap = eng.cfg.queue_capacity if capacity is None else capacity
        self.capacity = cap + slack  # physical rows, engine.DeviceQueue-style
        self.width = width
        self.buf = jnp.full(
            (eng.p, cap + slack, width), INVALID, jnp.int32, device=eng.sh(3)
        )
        self.n = jnp.zeros((eng.p,), jnp.int32, device=eng.sh(1))
        self._eng = eng
        self._max = 0
        self._dirty = False

    def set(self, buf: jax.Array, n: jax.Array) -> None:
        self.buf, self.n = buf, n
        self._dirty = True

    def set_n(self, n: jax.Array) -> None:
        self.n = n
        self._dirty = True

    def popped(self, n: jax.Array, k: int) -> None:
        """``n`` after every shard popped up to ``k`` rows: the new maximum
        follows from the cached one without a read from the device."""
        self.n = n
        if not self._dirty:
            self._max = max(self._max - k, 0)

    @property
    def max_n(self) -> int:
        if self._dirty:
            self._max = int(jnp.max(self.n))
            self._dirty = False
        return self._max

    def total(self) -> int:
        return int(jnp.sum(self.n))

    def free(self) -> int:
        """Physical free rows; gate ``free() >= worst_case_batch`` before
        running a producer (the Lemma 5.2 slack invariant)."""
        return self.capacity - self.max_n

    def drain(self) -> None:
        self.n = jnp.zeros((self._eng.p,), jnp.int32, device=self._eng.sh(1))
        self._max = 0
        self._dirty = False


# ---------------------------------------------------------------------------
# Operator runtimes (host-side wrappers over jitted shard_map step programs,
# implementing scheduler.OperatorRuntime)
# ---------------------------------------------------------------------------

class _DScanRT:
    def __init__(self, eng: "DistributedEngine", desc: OpDesc, out_q: _DQueue):
        self.e, self.desc, self.out_q = eng, desc, out_q
        self.label = desc.label()
        self.cursor = jnp.zeros((eng.p,), jnp.int32, device=eng.sh(1))
        self.rounds_done = 0
        self.delta = desc.scan_epoch == "delta"
        if self.delta and eng.delta_adj is None:
            raise RuntimeError(
                "delta-seeded scan on a distributed engine with no applied "
                "update batch — call DistributedEngine.apply_updates first"
            )
        # Rounds derive from the *current* batch size: scan sources are padded
        # to a multiple of the configured batch, and recovery only ever halves
        # it, so the division stays exact on degraded re-runs.
        scan_len = eng.delta_scan_len if self.delta else eng.scan_len
        self.rounds = scan_len // eng.cfg.batch_size
        self.step = eng._build_scan_step(desc)
        self.query = ""

    def has_input(self) -> bool:
        return self.rounds_done < self.rounds

    def internal_pending(self) -> bool:
        return self.has_input()

    def output_free(self) -> int:
        return self.out_q.free()

    def required_slack(self) -> int:
        return self.e.cfg.batch_size

    def run_one(self) -> None:
        e = self.e
        e._inject(("queue-overflow", "shard-loss"), self.label, self.query)
        if self.delta:
            src, dst, totals = e.delta_src, e.delta_dst, e.delta_scan_totals
        else:
            src, dst, totals = e.src, e.dst, e.scan_totals
        buf, n = self.step(
            src, dst, totals, self.cursor, self.out_q.buf, self.out_q.n
        )
        self.out_q.set(buf, n)
        self.cursor = self.cursor + e.cfg.batch_size
        self.rounds_done += 1
        e.stats["rounds"] += 1


class _DExtendRT:
    """PULL-EXTEND / VERIFY: fetch (2 a2a) + intersect + optional steal (2 a2a)."""

    def __init__(self, eng: "DistributedEngine", desc: OpDesc, in_q: _DQueue,
                 out_q: _DQueue):
        self.e, self.desc, self.in_q, self.out_q = eng, desc, in_q, out_q
        self.label = desc.label()
        self.is_verify = desc.kind == "verify"
        self.delta = "old" in desc.ext_epochs
        if self.delta and eng.delta_adj is None:
            raise RuntimeError(
                "old-epoch extend/verify on a distributed engine with no "
                "applied update batch — call apply_updates first"
            )
        self.step = eng._build_extend_step(desc, self.is_verify)
        self._ref_step = None  # lazily-built unfused twin (kernel-fail path)
        self.query = ""
        self.comm = jnp.zeros((2,), jnp.int32)  # [fetched, stolen], on device
        # The steal all_to_all is statically elided when a batch's worst-case
        # output can't be split P ways (mirrors the out_w >= p trace guard).
        self.steal_traced = (
            not self.is_verify
            and eng.cfg.rebalance
            and eng.cfg.batch_size * eng.d_pad >= eng.p
        )

    def has_input(self) -> bool:
        return self.in_q.max_n > 0

    def internal_pending(self) -> bool:
        return False

    def output_free(self) -> int:
        return self.out_q.free()

    def required_slack(self) -> int:
        b = self.e.cfg.batch_size
        return b if self.is_verify else b * self.e.d_pad

    def run_one(self) -> None:
        e = self.e
        e._inject(("queue-overflow", "shard-loss"), self.label, self.query)
        step = self.step
        if (
            e.cfg.fused
            and not self.delta
            and e.cfg.faults is not None
            and e.cfg.faults.should_fire("kernel-fail", self.label)
        ):
            # One-shot graceful degradation: re-run this batch through the
            # unfused (ref-twin) step program — exact, just slower.
            e.stats["kernel_fallbacks"] += 1
            _log.warning("fused %s kernel failed at op=%s query=%s; "
                         "falling back to ref step",
                         "verify" if self.is_verify else "extend",
                         self.label, self.query)
            if self._ref_step is None:
                self._ref_step = e._build_extend_step(
                    self.desc, self.is_verify, fallback=True
                )
            step = self._ref_step
        if self.delta:
            rem, buf, n, comm = step(
                e.delta_adj, e.adj, self.in_q.buf, self.in_q.n,
                self.out_q.buf, self.out_q.n,
            )
        else:
            rem, buf, n, comm = step(
                e.adj, self.in_q.buf, self.in_q.n, self.out_q.buf, self.out_q.n
            )
        self.in_q.popped(rem, e.cfg.batch_size)
        self.out_q.set(buf, n)
        self.comm = self.comm + jnp.sum(comm, axis=0)
        e.stats["rounds"] += 1
        e.stats["a2a_calls"] += 2 + (2 if self.steal_traced else 0)

    def finish_stats(self) -> None:
        """Fold the device-side traffic counters into the stats, once."""
        e = self.e
        fetched, stolen = (int(x) for x in np.asarray(self.comm))
        e.stats["pulled_vids"] += fetched
        e.stats["pulled_bytes"] += fetched * (e.d_pad + 2) * 4
        e.stats["steal_rows"] += stolen
        e.stats["steal_bytes"] += stolen * self.out_q.width * 4


class _DJoinRT:
    """PUSH-JOIN: hash-shuffle both inputs by join key (all_to_all), buffer
    them in preallocated [P, CAP, K] side buffers, and — once the left branch
    has drained (the §5.4 barrier) — sort the left side once and stream the
    right side through local probes."""

    def __init__(self, eng: "DistributedEngine", desc: OpDesc,
                 left_q: _DQueue, right_q: _DQueue, out_q: _DQueue):
        self.e, self.desc = eng, desc
        self.left_q, self.right_q, self.out_q = left_q, right_q, out_q
        self.label = desc.label()
        jcap = eng.cfg.join_buffer_capacity
        self.shuffle_slack = eng.p * eng.join_pop
        self.lbuf = _DQueue(eng, left_q.width, self.shuffle_slack, capacity=jcap)
        self.rbuf = _DQueue(eng, right_q.width, self.shuffle_slack, capacity=jcap)
        self.lshuf = eng._build_shuffle_step(desc.key_left[0])
        self.rshuf = eng._build_shuffle_step(desc.key_right[0])
        self.prep = eng._build_prepare_step(desc.key_left)
        self.probe = eng._build_probe_step(desc)
        self._ref_probe = None  # lazily-built unfused probe (kernel-fail path)
        self.query = ""
        self.moved = {w: jnp.zeros((), jnp.int32) for w in ("l", "r")}
        self._sorted: Optional[Tuple[jax.Array, jax.Array]] = None
        # installed by the engine: () -> bool, True once every ancestor of the
        # left input (and the left queue itself) has drained
        self.left_branch_done = lambda: True

    # -- scheduling interface ------------------------------------------------
    #
    # A join has three micro-operations with different output targets and
    # worst-case sizes (shuffle-left → lbuf, shuffle-right → rbuf, probe →
    # out_q), so capacity gating is internal: ``has_input`` reports pending
    # work, ``_runnable`` picks the next action that both has input *and*
    # fits, and output_free/required_slack degenerate to a 0/1 gate on it.
    # If work is pending but nothing fits (a genuinely overflowing side
    # buffer), the scheduler's stall guard raises — same contract as the
    # single-process queue-overflow error.

    def has_input(self) -> bool:
        return (
            self.left_q.max_n > 0
            or self.right_q.max_n > 0
            or (self.rbuf.max_n > 0 and self.left_branch_done())
        )

    def internal_pending(self) -> bool:
        # Rows shuffled but not yet probed keep this join's branch alive.
        return self.rbuf.max_n > 0

    def _runnable(self) -> Optional[str]:
        shuffle_slack = self.shuffle_slack
        if self.left_q.max_n > 0 and self.lbuf.free() >= shuffle_slack:
            return "lshuf"
        # Probing precedes shuffle-right so the probe drains rbuf and unblocks
        # further shuffles; it never competes with shuffle-left because the
        # barrier implies the left queue has drained.
        if (
            self.rbuf.max_n > 0
            and self.left_branch_done()
            and self.out_q.free() >= self.e.cfg.join_out_capacity
        ):
            return "probe"
        if self.right_q.max_n > 0 and self.rbuf.free() >= shuffle_slack:
            return "rshuf"
        return None

    def output_free(self) -> int:
        return 1 if self._runnable() is not None else 0

    def required_slack(self) -> int:
        return 1

    # -- execution -----------------------------------------------------------

    def _shuffle(self, step, in_q: _DQueue, side: _DQueue, which: str) -> None:
        e = self.e
        rem, buf, n, moved = step(in_q.buf, in_q.n, side.buf, side.n)
        in_q.popped(rem, e.join_pop)
        side.set(buf, n)
        assert self._sorted is None or side is self.rbuf, (
            "left side grew after the join barrier released"
        )
        self.moved[which] = self.moved[which] + jnp.sum(moved)
        e.stats["rounds"] += 1
        e.stats["a2a_calls"] += 1

    def finish_stats(self) -> None:
        e = self.e
        for which, side in (("l", self.lbuf), ("r", self.rbuf)):
            rows = int(self.moved[which])
            e.stats["shuffle_rows"] += rows
            e.stats["shuffle_bytes"] += rows * side.width * 4

    def run_one(self) -> None:
        e = self.e
        e._inject(("join-overflow", "shard-loss"), self.label, self.query)
        a = self._runnable()
        if a == "lshuf":
            self._shuffle(self.lshuf, self.left_q, self.lbuf, "l")
            return
        if a == "rshuf":
            self._shuffle(self.rshuf, self.right_q, self.rbuf, "r")
            return
        if self._sorted is None:
            # Barrier released: external merge sort of the buffered branch.
            self._sorted = self.prep(self.lbuf.buf, self.lbuf.n)
        probe = self.probe
        if (
            e.cfg.fused
            and e.cfg.faults is not None
            and e.cfg.faults.should_fire("kernel-fail", self.label)
        ):
            # One-shot fallback to the binary-search probe (exact ref twin).
            e.stats["kernel_fallbacks"] += 1
            _log.warning("probe bounds kernel failed at op=%s query=%s; "
                         "using ref probe", self.label, self.query)
            if self._ref_probe is None:
                self._ref_probe = e._build_probe_step(
                    self.desc, use_kernel_override=False
                )
            probe = self._ref_probe
        out_buf, out_n, rem, stuck = probe(
            self._sorted[0], self._sorted[1], self.rbuf.buf, self.rbuf.n,
            self.out_q.buf, self.out_q.n,
        )
        # A probe emits the matches of as many popped rows as fit in
        # join_out_capacity and leaves the rest in rbuf; it is stuck only
        # when one row alone has more matches than that.
        if bool(jnp.any(stuck)):
            raise QueuePressure(
                "join-overflow",
                "a distributed PUSH-JOIN probe row has more matches than "
                f"join_out_capacity={e.cfg.join_out_capacity}",
                op=self.label, query=self.query,
            )
        self.rbuf.set_n(rem)
        self.out_q.set(out_buf, out_n)
        e.stats["rounds"] += 1
        e.stats["probe_batches"] += 1


class _DSinkRT:
    def __init__(self, eng: "DistributedEngine", desc: OpDesc, in_q: _DQueue):
        self.e, self.desc, self.in_q = eng, desc, in_q
        self.label = desc.label()
        self.count = 0

    def has_input(self) -> bool:
        return self.in_q.max_n > 0

    def internal_pending(self) -> bool:
        return False

    def output_free(self) -> int:
        return 1 << 62

    def required_slack(self) -> int:
        return 0

    def run_one(self) -> None:
        self.count += self.in_q.total()
        self.in_q.drain()
        self.e.stats["rounds"] += 1


class DistributedEngine:
    """SPMD execution of translated dataflows over a ``shard_map`` mesh axis.

    Runs *any* optimiser plan — including hybrid plans mixing PULL-EXTEND and
    PUSH-JOIN — entirely with device collectives; there is no single-process
    fallback. ``stats["engine"]`` is always ``"shard_map"`` and
    ``stats["joins"]`` counts the PUSH-JOINs executed distributedly.
    """

    def __init__(self, graph: Graph, mesh: Mesh, cfg: DistConfig | None = None):
        self.cfg = cfg or DistConfig()
        self.mesh = mesh
        self.axis = self.cfg.axis
        self.p = mesh.shape[self.axis]
        self.sh = lambda ndim: NamedSharding(mesh, P(self.axis, *([None] * (ndim - 1))))
        self._load_graph(graph)
        # Delta state (streaming): armed by apply_updates.
        self.delta_adj: Optional[jax.Array] = None
        self.delta_src = self.delta_dst = self.delta_scan_totals = None
        self.delta_scan_len = 0
        self.stats: Dict[str, object] = {}

    def _sharded_edge_lists(self, graph: Graph):
        """Per-shard directed edge lists padded to the max shard size — the
        scan source layout, shared by the full graph and the delta graph.
        Built on the host and placed straight into their shards."""
        offsets = np.asarray(graph.offsets)
        deg_np = np.diff(offsets)
        src_all = np.repeat(np.arange(graph.num_vertices, dtype=np.int32), deg_np)
        dst_all = np.asarray(graph.nbrs, dtype=np.int32)
        owners = src_all % self.p
        b = self.cfg.batch_size
        max_e = max(int((owners == p).sum()) for p in range(self.p))
        max_e = max(b, ((max_e + b - 1) // b) * b)
        src = np.zeros((self.p, max_e), np.int32)
        dst = np.full((self.p, max_e), INVALID, np.int32)
        totals = np.zeros((self.p,), np.int32)
        for p in range(self.p):
            sel = owners == p
            n = int(sel.sum())
            src[p, :n] = src_all[sel]
            dst[p, :n] = dst_all[sel]
            totals[p] = n
        return (
            jax.device_put(src, self.sh(2)),
            jax.device_put(dst, self.sh(2)),
            jax.device_put(totals, self.sh(1)),
            max_e,
        )

    def _load_graph(self, graph: Graph) -> None:
        """(Re)partition and bind every graph-derived device array. The
        partition is built on the host and each shard's slice goes straight
        to its own device."""
        # The fetched table's keys run to 2·(P+1)·V (_fetch).
        assert 2 * (self.p + 1) * graph.num_vertices < 2 ** 31, (
            f"{graph.num_vertices} vertices on {self.p} shards overflow the "
            "int32 fetched-table keys"
        )
        pg = partition_graph(graph, self.p)
        self.graph = graph
        self.v = graph.num_vertices
        self.d_pad = pg.d_pad
        self.adj = jax.device_put(pg.adj, self.sh(3))
        self.src, self.dst, self.scan_totals, self.scan_len = (
            self._sharded_edge_lists(graph)
        )

    @property
    def join_pop(self) -> int:
        """Rows a PUSH-JOIN shuffle or probe step takes from each shard: the
        row budget of one extend step's output (batch × d_pad), spread over
        the shards, so a shuffle's exchange is as large as an extend's."""
        b = self.cfg.batch_size
        return max(b, b * self.d_pad // self.p)

    # -- streaming updates (DESIGN.md §Delta-plans) ----------------------------

    def apply_updates(self, batch):
        """Apply an edge-insert batch on the distributed engine.

        The storage rebuild itself is row-local (graph/storage.apply_updates);
        the shard partition is then re-derived — vertex ownership is ``v % P``
        so ownership never moves, only the owners' padded rows change. The
        delta graph is kept two ways: its directed edges sharded by owner
        exactly like normal scan sources (delta scans are sharded scans), and
        its padded adjacency **replicated** on every shard for the old-epoch
        membership veto — delta batches are small, so replication is cheaper
        than a second fetch round per extend."""
        from repro.graph.storage import apply_updates as storage_apply_updates

        applied = storage_apply_updates(self.graph, batch)
        self._load_graph(applied.graph)
        delta = applied.delta
        self.delta_adj = jax.device_put(
            delta.padded.adj, NamedSharding(self.mesh, P())
        )
        (
            self.delta_src,
            self.delta_dst,
            self.delta_scan_totals,
            self.delta_scan_len,
        ) = self._sharded_edge_lists(delta)
        return applied

    # ------------------------------------------------------------------
    # shard-local pieces (inside shard_map; no leading P dim)
    # ------------------------------------------------------------------

    def _offshard_count(self, mask):
        """Number of True entries in a per-destination ``[P, ...]`` mask whose
        destination is not this shard — the cross-network share of an
        all_to_all, for traffic accounting."""
        me = jax.lax.axis_index(self.axis)
        dest = jnp.arange(self.p).reshape((self.p,) + (1,) * (mask.ndim - 1))
        return jnp.sum((mask & (dest != me)).astype(jnp.int32))

    def _fetch(self, adj, rows, valid_rows, ext):
        """Fetch stage: dedup needed vids, owner-routed exchange, return a
        lookup table (sorted keys, adjacency rows) plus the number of
        requests this shard routed to *other* shards (pull-traffic
        accounting).

        The request lists come out of one sort by ``(owner, vid)``, so the
        returned rows are already in key order: the table is searched by
        ``_table_index`` without sorting it again (a sort of ``P·B·E`` keys
        costs the TPU compiler seconds per shape, and reordering the rows
        would copy the whole fetched table)."""
        p, axis = self.p, self.axis
        vids = rows[:, list(ext)].reshape(-1)
        ok = (
            (vids != INVALID)
            & (vids >= 0)
            & jnp.repeat(valid_rows[:, None], len(ext), 1).reshape(-1)
        )
        r_cap = vids.shape[0]
        owner = jnp.where(ok, vids % p, p)
        key = jnp.where(ok, owner * self.v + vids, p * self.v)
        skey = jnp.sort(key)
        uniq = (skey < p * self.v) & jnp.concatenate(
            [jnp.ones((1,), bool), skey[1:] != skey[:-1]]
        )
        o_s = jnp.where(uniq, skey // self.v, p)
        v_s = jnp.where(uniq, skey % self.v, INVALID)
        cnt = jax.ops.segment_sum(uniq.astype(jnp.int32), o_s, num_segments=p + 1)[:p]
        offs = jnp.cumsum(cnt) - cnt
        rank = jnp.cumsum(uniq.astype(jnp.int32)) - 1
        slot = rank - jnp.take(
            jnp.concatenate([offs, jnp.zeros(1, jnp.int32)]), jnp.minimum(o_s, p)
        )
        reqs = jnp.full((p, r_cap), INVALID, jnp.int32).at[
            jnp.where(uniq, o_s, p), jnp.where(uniq, slot, r_cap)
        ].set(v_s, mode="drop")
        remote = self._offshard_count(reqs != INVALID)
        got = jax.lax.all_to_all(reqs, axis, split_axis=0, concat_axis=0, tiled=True)
        lid = jnp.clip(jnp.where(got != INVALID, got // p, 0), 0, adj.shape[0] - 1)
        served = jnp.take(adj, lid.reshape(-1), axis=0).reshape(p, r_cap, -1)
        served = jnp.where((got != INVALID)[:, :, None], served, INVALID)
        back = jax.lax.all_to_all(served, axis, split_axis=0, concat_axis=0, tiled=True)
        # Row o of ``reqs`` holds owner o's vids ascending, INVALID-padded:
        # key 2·(o·V + vid) orders the flattened table, and padding gets
        # 2·(o·V + V) − 1, which sorts after row o and before row o + 1.
        owner_base = (jnp.arange(p, dtype=jnp.int32) * self.v)[:, None]
        keys = jnp.where(reqs != INVALID, 2 * (owner_base + reqs),
                         2 * (owner_base + self.v) - 1)
        return keys.reshape(-1), back.reshape(-1, adj.shape[-1]), remote

    def _table_index(self, table_keys, vids):
        """Row of each vid in the fetched table, and whether it is there."""
        q = 2 * ((vids % self.p) * self.v + vids)
        idx = jnp.clip(jnp.searchsorted(table_keys, q), 0, table_keys.shape[0] - 1)
        return idx, (jnp.take(table_keys, idx) == q) & (vids >= 0) & (vids != INVALID)

    def _lookup(self, table_keys, table_rows, adj, vids):
        p = self.p
        me = jax.lax.axis_index(self.axis)
        ok = (vids != INVALID) & (vids >= 0)
        local = ok & ((vids % p) == me)
        lrows = jnp.take(
            adj, jnp.clip(jnp.where(ok, vids // p, 0), 0, adj.shape[0] - 1), axis=0
        )
        idx, hit = self._table_index(table_keys, vids)
        rrows = jnp.take(table_rows, idx, axis=0)
        rows = jnp.where(local[:, None], lrows, jnp.where(hit[:, None], rrows, INVALID))
        return jnp.where(ok[:, None], rows, INVALID)

    def _fused_addressing(self, table_keys, adj, rows, ext):
        """The _lookup gather as fused-kernel slab addressing: tab0 = fetched
        remote table, tab1 = local adjacency. Returns (idx[2, B, E], sel, ok)
        with sel routing remote hits to the table and ok covering exactly the
        rows _lookup would return non-INVALID (local or fetched)."""
        p = self.p
        me = jax.lax.axis_index(self.axis)
        vids = rows[:, list(ext)]                       # [B, E]
        okv = (vids != INVALID) & (vids >= 0)
        local = okv & ((vids % p) == me)
        idx1 = jnp.clip(jnp.where(okv, vids // p, 0), 0, adj.shape[0] - 1)
        idx0, hit = self._table_index(table_keys, vids)
        sel = (~local) & hit
        ok = okv & (local | hit)
        idx = jnp.stack([idx0.astype(jnp.int32), idx1.astype(jnp.int32)])
        return idx, sel.astype(jnp.int32), ok.astype(jnp.int32)

    # ------------------------------------------------------------------
    # jitted shard_map step programs
    # ------------------------------------------------------------------

    def _shardmap(self, f, n_in, n_out):
        ax = self.axis
        return jax.jit(
            shard_map(
                f,
                mesh=self.mesh,
                in_specs=tuple(P(ax) for _ in range(n_in)),
                out_specs=tuple(P(ax) for _ in range(n_out)) if n_out > 1 else P(ax),
                check_vma=False,
            )
        )

    def _build_scan_step(self, op: OpDesc):
        b = self.cfg.batch_size
        lt, gt = op.lt_positions, op.gt_positions

        def f(src, dst, total, cursor, qbuf, qn):
            rows, n = ops_mod.scan_batch(src[0], dst[0], cursor[0], total[0], b, lt, gt)
            buf, n2 = ops_mod.queue_append(qbuf[0], qn[0], rows, n)
            return buf[None], n2[None]

        return self._shardmap(f, 6, 2)

    def _build_extend_step(self, op: OpDesc, is_verify: bool,
                           fallback: bool = False):
        """``fallback`` builds the kernel-free twin a failed kernel degrades
        to: unfused, with the XLA binary-search membership."""
        from repro.kernels.intersect import ops as ik

        b = self.cfg.batch_size
        ext, lt, gt = op.ext, op.lt_positions, op.gt_positions
        vpos = op.verify_pos
        rebalance = self.cfg.rebalance
        fused = self.cfg.fused and not fallback
        force_kernel = self.cfg.force_kernel
        p = self.p
        # Old-epoch ops veto delta membership against the *replicated* delta
        # adjacency (spec P() below); the fused kernels know nothing of
        # epochs, so epoch-carrying ops always take the plain intersect path.
        old_mask = tuple(ep == "old" for ep in op.ext_epochs) or (False,) * len(ext)
        has_old = any(old_mask)
        if has_old:
            fused = False

        def f(delta_adj, adj3, in_buf, in_n, out_buf, out_n):
            adj = adj3[0]

            def delta_rows(vids):
                safe = jnp.clip(vids, 0, delta_adj.shape[0] - 1)
                r = jnp.take(delta_adj, safe, axis=0)
                ok = (vids >= 0) & (vids != INVALID)
                return jnp.where(ok[:, None], r, INVALID)

            rows, take, rem = ops_mod.queue_pop(in_buf[0], in_n[0], b)
            valid = jnp.arange(b) < take
            tv, tr, remote = self._fetch(adj, rows, valid, ext)
            stolen = jnp.zeros((), jnp.int32)
            k = rows.shape[1]
            if is_verify and fused:
                idx, sel, okm = self._fused_addressing(tv, adj, rows, ext)
                mask = valid & ik.fused_verify(
                    tr, adj, idx, sel, okm, rows, vpos=vpos,
                    force_kernel=force_kernel,
                )
                new_rows, m = ops_mod.compact(rows, mask, b)
                out_w = b
            elif is_verify:
                target = rows[:, vpos : vpos + 1]
                mask = valid
                for d, is_old in zip(ext, old_mask):
                    other = self._lookup(tv, tr, adj, rows[:, d])
                    mask = mask & ops_mod.row_membership(other, target)[:, 0]
                    if is_old:
                        mask = mask & ~ops_mod.row_membership(
                            delta_rows(rows[:, d]), target
                        )[:, 0]
                new_rows, m = ops_mod.compact(rows, mask, b)
                out_w = b
            else:
                if fused:
                    idx, sel, okm = self._fused_addressing(tv, adj, rows, ext)
                    cands, mask = ik.fused_extend(
                        tr, adj, idx, sel, okm, rows, lt=lt, gt=gt,
                        force_kernel=force_kernel,
                    )
                    mask = mask & valid[:, None]
                else:
                    cands = self._lookup(tv, tr, adj, rows[:, ext[0]])
                    mask = (cands != INVALID) & valid[:, None]
                    others = [self._lookup(tv, tr, adj, rows[:, d]) for d in ext[1:]]
                    if fallback:
                        for other in others:
                            mask = mask & ops_mod.row_membership(other, cands)
                    elif len(ext) > 1:
                        # Native membership kernel on the TPU, where the
                        # binary search is gather-bound; the jnp twin elsewhere.
                        mask = mask & ik.multiway_membership(
                            cands, jnp.stack(others, axis=1))
                    for d, is_old in zip(ext, old_mask):
                        if is_old:
                            mask = mask & ~ops_mod.row_membership(
                                delta_rows(rows[:, d]), cands
                            )
                    for col in range(k):
                        mask = mask & (cands != rows[:, col : col + 1])
                    for pp in lt:
                        mask = mask & (cands < jnp.where(valid, rows[:, pp], -1)[:, None])
                    for pp in gt:
                        mask = mask & (cands > jnp.where(valid, rows[:, pp], INVALID)[:, None])
                d_pad = cands.shape[1]
                expanded = jnp.concatenate(
                    [jnp.broadcast_to(rows[:, None, :], (b, d_pad, k)), cands[:, :, None]],
                    axis=2,
                ).reshape(b * d_pad, k + 1)
                new_rows, m = ops_mod.compact(expanded, mask.reshape(-1), b * d_pad)
                out_w = b * d_pad
                k = k + 1
            if rebalance and not is_verify and out_w >= p:
                share = out_w // p
                chunks = new_rows[: share * p].reshape(p, share, k)
                cvalid = (jnp.arange(share * p) < m).reshape(p, share)
                stolen = self._offshard_count(cvalid)
                got = jax.lax.all_to_all(chunks, self.axis, split_axis=0, concat_axis=0, tiled=True)
                gvalid = jax.lax.all_to_all(cvalid, self.axis, split_axis=0, concat_axis=0, tiled=True)
                new_rows, m = ops_mod.compact(got.reshape(-1, k), gvalid.reshape(-1), out_w)
            buf, n2 = ops_mod.queue_append(out_buf[0], out_n[0], new_rows, m)
            comm = jnp.stack([remote, stolen])[None]  # [1, 2]
            return rem[None], buf[None], n2[None], comm

        ax = self.axis
        if has_old:
            # Replicated delta adjacency: spec P() — every shard reads the
            # whole (small) delta table for its old-epoch membership vetoes.
            return jax.jit(
                shard_map(
                    f,
                    mesh=self.mesh,
                    in_specs=(P(),) + tuple(P(ax) for _ in range(5)),
                    out_specs=tuple(P(ax) for _ in range(4)),
                    check_vma=False,
                )
            )

        def g(adj3, in_buf, in_n, out_buf, out_n):
            return f(
                jnp.full((1, 1), INVALID, jnp.int32), adj3, in_buf, in_n,
                out_buf, out_n,
            )

        return self._shardmap(g, 5, 4)

    def _build_shuffle_step(self, key_col: int):
        """Pop a batch from an input queue, hash-route each row to shard
        ``row[key_col] % P`` with one all_to_all, append arrivals to the join
        side buffer. Also returns the number of rows that crossed shards."""
        b = self.join_pop
        p = self.p

        def f(in_buf, in_n, side_buf, side_n):
            rows, take, rem = ops_mod.queue_pop(in_buf[0], in_n[0], b)
            valid = jnp.arange(b) < take
            send = ops_mod.partition_rows_by_key(rows, valid, rows[:, key_col], p)
            moved = self._offshard_count(send[:, :, 0] != INVALID)
            got = jax.lax.all_to_all(send, self.axis, split_axis=0, concat_axis=0, tiled=True)
            flat = got.reshape(p * b, rows.shape[1])
            packed, m = ops_mod.compact(flat, flat[:, 0] != INVALID, p * b)
            buf, n2 = ops_mod.queue_append(side_buf[0], side_n[0], packed, m)
            return rem[None], buf[None], n2[None], moved[None]

        return self._shardmap(f, 4, 4)

    def _build_prepare_step(self, key_cols: Tuple[int, ...]):
        def f(side_buf, side_n):
            keys, sorted_buf = ops_mod.join_prepare(side_buf[0], side_n[0], key_cols)
            return keys[None], sorted_buf[None]

        return self._shardmap(f, 2, 2)

    def _build_probe_step(self, op: OpDesc,
                          use_kernel_override: Optional[bool] = None):
        b = self.join_pop
        out_cap = self.cfg.join_out_capacity
        key_right, right_extra = op.key_right, op.right_extra
        cross_neq, cross_lt = op.cross_neq, op.cross_lt

        use_kernel, force_kernel = self.cfg.fused, self.cfg.force_kernel
        if use_kernel_override is not None:
            use_kernel = use_kernel_override

        def f(skeys, sbuf, r_buf, r_n, out_buf, out_n):
            rrows, take, rem = ops_mod.queue_pop(r_buf[0], r_n[0], b)
            out, m, left = ops_mod.join_probe(
                skeys[0], sbuf[0], rrows, take,
                key_right, right_extra, cross_neq, cross_lt, out_cap,
                use_kernel=use_kernel, force_kernel=force_kernel,
            )
            buf, n2 = ops_mod.queue_append(out_buf[0], out_n[0], out, m)
            stuck = (take > 0) & (left == take)
            return buf[None], n2[None], (rem + left)[None], stuck[None]

        return self._shardmap(f, 6, 4)

    # ------------------------------------------------------------------

    def _build_runtimes(
        self, flow: Dataflow, tenant_of_op: Optional[Tuple[int, ...]] = None
    ) -> List[object]:
        ops = flow.ops
        b = self.cfg.batch_size
        queues: Dict[int, _DQueue] = {}
        for i, op in enumerate(ops):
            if op.kind == "sink":
                continue
            slack = {
                "scan": b,
                "verify": b,
                "extend": b * self.d_pad,
                "join": self.cfg.join_out_capacity,
            }[op.kind]
            queues[i] = _DQueue(self, len(op.schema), slack)

        runtimes: List[object] = []
        for i, op in enumerate(ops):
            if op.kind == "scan":
                rt = _DScanRT(self, op, queues[i])
            elif op.kind in ("extend", "verify"):
                rt = _DExtendRT(self, op, queues[op.inputs[0]], queues[i])
            elif op.kind == "join":
                rt = _DJoinRT(
                    self, op, queues[op.inputs[0]], queues[op.inputs[1]], queues[i]
                )
            else:
                rt = _DSinkRT(self, op, queues[op.inputs[0]])
            runtimes.append(rt)

        # Tenant tags for mixed traffic (run_concurrent): every queue and
        # runtime of a merged flow carries its tenant id. Rows themselves
        # never mix queues — each [P, CAP, K] buffer belongs to exactly one
        # tenant's op — so the tag lives on the queue, not as a +1 row column
        # that would widen every shuffle for information the queue already
        # encodes (DESIGN.md §Graph-service).
        for i, rt in enumerate(runtimes):
            t = 0 if tenant_of_op is None else tenant_of_op[i]
            rt.tenant = t
            rt.query = flow.query_name
            if tenant_of_op is not None:
                rt.label = f"t{t}:{rt.label}"
            if i in queues:
                queues[i].tenant = t

        # Join barriers: probing may start only once every ancestor of the
        # left input has drained — no scans pending, no queued rows, no
        # unprobed rows inside ancestor joins.
        for i, op in enumerate(ops):
            if op.kind != "join":
                continue
            branch = (*flow.ancestors(op.inputs[0]), op.inputs[0])

            def make_done(branch=branch):
                def done() -> bool:
                    for j in branch:
                        if runtimes[j].internal_pending():
                            return False
                        if j in queues and queues[j].max_n > 0:
                            return False
                    return True
                return done

            runtimes[i].left_branch_done = make_done()
        return runtimes

    def run(
        self,
        query_or_plan: QueryGraph | ExecutionPlan | Dataflow,
        space: str = "huge",
    ) -> Tuple[int, Dict]:
        """Plan (if needed), translate, and execute on the mesh. Returns
        ``(count, stats)``; stats always reports ``engine="shard_map"`` — every
        operator, PUSH-JOIN included, ran with real collectives."""
        flow = self._to_flow(query_or_plan, space)
        sinks = flow.sink_indices()
        if len(sinks) != 1:
            raise ValueError(
                f"run() got a flow with {len(sinks)} sinks — merged multi-sink "
                "flows carry one result per source flow; use run_concurrent "
                "(per-tenant counts) or run_delta (delta unions) instead"
            )
        runtimes, st = self._execute(flow)
        sink = runtimes[sinks[0]]
        assert isinstance(sink, _DSinkRT)
        return sink.count, self.stats

    def run_concurrent(
        self,
        queries: List[QueryGraph | ExecutionPlan | Dataflow],
        space: str = "huge",
    ) -> Tuple[List[int], Dict]:
        """Serve N tenants' queries through ONE engine instance: the flows are
        merged into a single multi-sink DAG (dataflow.merge_flows) and one
        AdaptiveScheduler pass interleaves their SPMD steps — mixed traffic on
        shared collectives, with tenant-tagged queues/runtimes keeping results
        and accounting separable. Returns per-tenant counts in input order."""
        flows = [self._to_flow(q, space) for q in queries]
        merged, tenant_of_op = merge_flows(flows)
        verify_flow(merged)  # the merged multi-sink DAG must also be well-formed
        runtimes, st = self._execute(merged, tenant_of_op)
        counts = []
        for i in merged.sink_indices():
            sink = runtimes[i]
            assert isinstance(sink, _DSinkRT)
            counts.append(sink.count)
        self.stats["tenants"] = len(flows)
        self.stats["per_tenant_matches"] = list(counts)
        return counts, self.stats

    def run_delta(
        self,
        query_or_plan: QueryGraph | ExecutionPlan,
        space: str = "huge",
    ) -> Tuple[int, Dict]:
        """Count only the matches created by the last applied batch, SPMD.

        The delta-join decomposition (dataflow.delta_flows) is merged into one
        multi-sink DAG — delta scans are sharded by edge owner exactly like
        normal scans, old-epoch extends veto against the replicated delta
        adjacency — and executed by the same scheduler pass as run(). Returns
        the summed delta count (the union of the k flows is disjoint by the
        exactly-once rule) plus the usual traffic stats."""
        if self.delta_adj is None:
            raise RuntimeError(
                "run_delta before apply_updates: no delta batch is armed"
            )
        if isinstance(query_or_plan, QueryGraph):
            plan = optimal_plan(
                query_or_plan, GraphStats.from_graph(self.graph), self.p, space
            )
        elif isinstance(query_or_plan, ExecutionPlan):
            plan = query_or_plan
        else:
            raise TypeError(
                "run_delta needs a QueryGraph or ExecutionPlan (delta flows "
                "are derived from the query, not from an existing Dataflow)"
            )
        from repro.core.dataflow import delta_flows

        flows = delta_flows(plan)
        merged, tenant_of_op = merge_flows(flows)
        verify_flow(merged)
        runtimes, st = self._execute(merged, tenant_of_op)
        count = 0
        for i in merged.sink_indices():
            sink = runtimes[i]
            assert isinstance(sink, _DSinkRT)
            count += sink.count
        self.stats["delta_flows"] = len(flows)
        return count, self.stats

    def _to_flow(
        self, query_or_plan: QueryGraph | ExecutionPlan | Dataflow, space: str
    ) -> Dataflow:
        if isinstance(query_or_plan, Dataflow):
            flow = query_or_plan
        else:
            if isinstance(query_or_plan, QueryGraph):
                plan = optimal_plan(
                    query_or_plan, GraphStats.from_graph(self.graph), self.p, space
                )
            else:
                plan = query_or_plan
            flow = translate(plan)
        # Mandatory pre-flight: structural verification before any device
        # work (queue pricing is the single-host engine's concern).
        verify_flow(flow)
        return flow

    # -- fault injection (core/faults.py) --------------------------------------

    def _inject(self, kinds: Tuple[str, ...], op: str, query: str = "") -> None:
        """Probe the armed FaultPlan at an operator invocation and raise the
        matching structured fault (host-side only; never inside shard_map)."""
        fp = self.cfg.faults
        if fp is None:
            return
        for kind in kinds:
            if fp.should_fire(kind, op):
                if kind == "shard-loss":
                    raise ShardLoss(fp.seed % self.p, op=op, query=query)
                raise QueuePressure(kind, "injected fault", op=op, query=query)

    def _execute(
        self, flow: Dataflow, tenant_of_op: Optional[Tuple[int, ...]] = None
    ):
        """Build runtimes and drive one scheduler pass, with restart-based
        recovery (DESIGN.md §Fault-tolerance): SPMD execution is
        deterministic, so a recoverable fault rebuilds the runtimes — fresh
        queues, zero counts — and re-runs the whole flow, halving the batch
        on QueuePressure. The original config is restored on exit, so
        degradation never leaks across queries."""
        orig_cfg = self.cfg
        attempts = restarts = pressure = 0
        try:
            while True:
                # Release the previous run's runtimes (and device queues)
                # before allocating fresh ones, so back-to-back runs/retries
                # don't hold both sets.
                self._last_runtimes = None
                self.stats = {
                    "engine": "shard_map",
                    "shards": self.p,
                    "joins": flow.num_joins(),
                    "rounds": 0,
                    "a2a_calls": 0,
                    "pulled_vids": 0,
                    "pulled_bytes": 0,
                    "shuffle_rows": 0,
                    "shuffle_bytes": 0,
                    "steal_rows": 0,
                    "steal_bytes": 0,
                    "probe_batches": 0,
                    "kernel_fallbacks": 0,
                    "retries": attempts,
                    "restarts": restarts,
                    "pressure_events": pressure,
                }
                runtimes = self._build_runtimes(flow, tenant_of_op)
                self._last_runtimes = runtimes  # debugging / test introspection
                sched = AdaptiveScheduler(runtimes, dfs_bias=attempts > 0)
                try:
                    st = sched.run()
                except EnumerationFault as f:
                    if (
                        not orig_cfg.recover
                        or not f.recoverable
                        or attempts >= orig_cfg.max_retries
                    ):
                        raise
                    attempts += 1
                    if isinstance(f, ShardLoss):
                        restarts += 1
                        _log.warning(
                            "restarting after %s (attempt %d/%d)",
                            f, attempts, orig_cfg.max_retries,
                        )
                    else:
                        pressure += 1
                        nb = max(self.cfg.batch_size // 2,
                                 orig_cfg.min_batch_size)
                        if nb >= self.cfg.batch_size:
                            raise EnumerationFault(
                                f.kind,
                                "recovery ladder exhausted: batch already at "
                                f"floor {self.cfg.batch_size} (raise queue "
                                "capacities or min_batch_size)",
                                op=f.op, query=f.query,
                            ) from f
                        _log.warning(
                            "restarting after %s (attempt %d/%d): "
                            "batch %d -> %d", f, attempts,
                            orig_cfg.max_retries, self.cfg.batch_size, nb,
                        )
                        self.cfg = dataclasses.replace(self.cfg, batch_size=nb)
                    continue
                for rt in runtimes:
                    if hasattr(rt, "finish_stats"):
                        rt.finish_stats()
                self.stats["sched_steps"] = st.steps
                self.stats["sched_backtracks"] = st.backtracks
                return runtimes, st
        finally:
            self.cfg = orig_cfg
