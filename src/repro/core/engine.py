"""The HUGE engine: dataflow execution with the adaptive scheduler (§4-§5).

This is the single-process reference engine. It executes the full dataflow on
one device while *simulating* the k-machine deployment for communication
accounting exactly as the paper measures it:

  * partial results live on the machine owning their first matched vertex
    (SCAN emits edges from the owner's partition; PULL-EXTEND keeps results
    local; PUSH-JOIN re-partitions by join key);
  * a PULL-EXTEND's fetch stage dedups the batch's remote vertices per
    machine (the paper's merged-RPC aggregation) and runs them through a
    per-machine LRBU cache; cache misses are charged
    ``(deg(v) + 2) * 4`` bytes of pull traffic;
  * PUSH-JOIN charges the shuffle of both inputs; pushing-mode wco extends
    (BiGJoin-style plans) charge ``|ext| · rows · K`` words.

Counts are exact (validated against the networkx oracle); communication and
memory are measured the way Table 1 reports C and M. The true multi-device
engine with real collectives is distributed.py.
"""
from __future__ import annotations

import copy
import dataclasses
import functools
import logging
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cache as lrbu
from repro.core.faults import (
    EnumerationFault,
    FaultPlan,
    KernelFault,
    QueuePressure,
    ShardLoss,
)
from repro.core import operators as ops_mod
from repro.core.cost import GraphStats
from repro.core.dataflow import (
    Dataflow,
    OpDesc,
    delta_flows,
    merge_flows,
    translate,
)
from repro.core.optimizer import optimal_plan
from repro.core.plan import ExecutionPlan
from repro.core.query import QueryGraph
from repro.core.scheduler import AdaptiveScheduler, ScheduleStats
from repro.core.tracing import host_read, span
from repro.graph.storage import (
    AppliedUpdates,
    Graph,
    GraphUpdateBatch,
    INVALID,
    apply_updates as storage_apply_updates,
)

_log = logging.getLogger("repro.engine")


# ---------------------------------------------------------------------------
# Config / stats
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EngineConfig:
    batch_size: int = 256
    queue_capacity: int = 1 << 17          # rows per operator output queue
    join_buffer_capacity: int = 1 << 20    # rows buffered per PUSH-JOIN input
    join_out_capacity: int = 1 << 18       # worst-case rows per join step
    num_machines: int = 8                  # simulated cluster size (k)
    cache_capacity: int = 1 << 14          # entries per machine (0 = disabled)
    cache_ways: int = 4
    cache_policy: str = "lrbu"             # "lrbu" | "lru" | "direct"
    materialize: bool = False              # keep final matches (tests only)
    materialize_cap: int = 1 << 20
    fused: bool = False                    # fused hot path: LRBU value-cache
    #   probe → slab gather → intersect in one kernel pass (extend/verify) and
    #   the compare-count bounds kernel inside PUSH-JOIN probes
    force_kernel: bool = False             # run fused kernels in interpret mode
    #   on CPU (CI parity); otherwise non-TPU backends use the ref twins
    faults: Optional[FaultPlan] = None     # deterministic fault injection
    recover: bool = True                   # graceful-degradation ladder on
    #   recoverable faults (DESIGN.md §Fault-tolerance); False = fail fast
    max_retries: int = 4                   # recovery attempts per driven run
    min_batch_size: int = 32               # degradation floor for batch halving
    checkpoint_every_steps: int = 0        # snapshot cadence inside drive()
    #   (0 = a single snapshot at start; a crash replays the whole query)


@dataclasses.dataclass
class EngineStats:
    count: int = 0
    pulled_bytes: int = 0
    pushed_bytes: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    batches: int = 0
    rows_emitted: int = 0
    host_syncs: int = 0         # device-to-host reads (tracing.host_read)
    peak_queue_rows: int = 0
    peak_queue_bytes: int = 0
    join_overflows: int = 0
    kernel_fallbacks: int = 0   # fused-kernel failures degraded to the ref twin
    pressure_events: int = 0    # QueuePressure signals absorbed by recovery
    retries: int = 0            # checkpoint restores (pressure + shard loss)
    restarts: int = 0           # of which: shard-loss recoveries
    wall_time: float = 0.0
    per_machine_rows: Optional[np.ndarray] = None

    @property
    def total_comm_bytes(self) -> int:
        return self.pulled_bytes + self.pushed_bytes

    @property
    def hit_rate(self) -> float:
        tot = self.cache_hits + self.cache_misses
        return self.cache_hits / tot if tot else 0.0


@dataclasses.dataclass
class EnumerationResult:
    count: int
    stats: EngineStats
    schedule: ScheduleStats
    matches: Optional[np.ndarray] = None  # [n, |V_q|] columns in query-vertex order


# ---------------------------------------------------------------------------
# Request routing (fetch stage, Alg. 4 lines 1-9)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_machines", "num_vertices", "r_cap"))
def route_requests(vids, machs, valid, num_machines: int, num_vertices: int, r_cap: int):
    """Dedup (machine, vid) request pairs into per-machine fixed-width lists."""
    big = jnp.int32(num_machines * num_vertices)
    key = jnp.where(valid, machs * num_vertices + vids, big)
    order = jnp.argsort(key)
    ks = jnp.take(key, order)
    valid_s = ks < big
    uniq = valid_s & jnp.concatenate([jnp.ones((1,), bool), ks[1:] != ks[:-1]])
    m_s = jnp.where(valid_s, ks // num_vertices, num_machines)
    v_s = jnp.where(valid_s, ks % num_vertices, INVALID)
    cnt = jax.ops.segment_sum(uniq.astype(jnp.int32), m_s, num_segments=num_machines + 1)[
        :num_machines
    ]
    offs = jnp.cumsum(cnt) - cnt
    offs_ext = jnp.concatenate([offs, jnp.zeros((1,), jnp.int32)])
    grank = jnp.cumsum(uniq.astype(jnp.int32)) - 1
    slot = grank - jnp.take(offs_ext, jnp.minimum(m_s, num_machines))
    tgt_m = jnp.where(uniq, m_s, num_machines)
    tgt_s = jnp.where(uniq, slot, r_cap)
    reqs = jnp.full((num_machines, r_cap), INVALID, jnp.int32).at[tgt_m, tgt_s].set(
        v_s, mode="drop"
    )
    return reqs, cnt


def _make_stacked_cache(num_machines: int, capacity: int, ways: int) -> lrbu.LRBUState:
    sets = max(1, capacity // ways)
    return lrbu.LRBUState(
        keys=jnp.full((num_machines, sets, ways), INVALID, jnp.int32),
        epoch=jnp.full((num_machines, sets, ways), -1, jnp.int32),
        current_epoch=jnp.zeros((num_machines,), jnp.int32),
    )


_POLICIES = {
    "lrbu": lrbu.fetch_update,
    "lru": lrbu.fetch_update_lru,
    "direct": lrbu.fetch_update_direct,
}


@functools.partial(
    jax.jit,
    static_argnames=("ext", "num_machines", "num_vertices", "policy"),
    donate_argnums=(0,),
)
def _fetch_accounting(cache, deg, rows, n, ext: Tuple[int, ...],
                      num_machines: int, num_vertices: int, policy):
    """One batch of the simulated fetch stage as a single program: route the
    remote vertices, run them through the per-machine caches (``policy``
    None = no cache), and return the updated caches with ``[pulled_bytes,
    hits, misses]`` — read back by the host in one transfer."""
    b = rows.shape[0]
    row_valid = jnp.arange(b) < n
    shard = jnp.where(rows[:, 0] >= 0, rows[:, 0] % num_machines, 0)
    vids = rows[:, list(ext)]                       # [B, E]
    machs = jnp.broadcast_to(shard[:, None], vids.shape)
    remote = (vids % num_machines) != machs
    valid = row_valid[:, None] & (vids != INVALID) & (vids >= 0) & remote
    vids_f = vids.reshape(-1)
    reqs, _ = route_requests(
        vids_f, machs.reshape(-1), valid.reshape(-1), num_machines,
        num_vertices, r_cap=vids_f.shape[0],
    )
    req_valid = reqs != INVALID
    if policy is not None:
        cache, hit = jax.vmap(_POLICIES[policy])(cache, reqs)
        hit = hit & req_valid
    else:
        hit = jnp.zeros_like(req_valid)
    miss = req_valid & ~hit
    degs = jnp.where(miss, jnp.take(deg, jnp.clip(reqs, 0, num_vertices - 1)), 0)
    pulled = jnp.sum((degs + 2) * 4 * miss)
    return cache, jnp.stack([pulled, jnp.sum(hit), jnp.sum(miss)]).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("ext", "cached"), donate_argnums=(0,))
def _fused_addressing(vcache, adj, deg, rows, ext: Tuple[int, ...], cached: bool):
    """Slab addressing of the fused kernels for one batch, as one program:
    insert the batch's deduped vertices into the LRBU value cache (when
    ``cached``), then probe it. Returns ``(vcache, idx[2, B, E], sel, ok)``:
    hits read cache slabs (tab0), misses the adjacency table (tab1)."""
    v = adj.shape[0]
    vids = rows[:, list(ext)]                       # [B, E]
    ok = (vids >= 0) & (vids < v)
    idx1 = jnp.clip(vids, 0, v - 1)
    if cached:
        flat = jnp.where(ok, vids, INVALID).reshape(-1)
        uniq = ops_mod.dedup_pad(flat)
        safe = jnp.clip(uniq, 0, v - 1)
        slabs = jnp.take(adj, safe, axis=0)
        degs = jnp.where(uniq != INVALID, jnp.take(deg, safe), 0)
        vcache, _ = lrbu.fetch_update_values(vcache, uniq, slabs, degs)
        idx0, hit = lrbu.probe_indices(vcache, flat)
        idx0 = idx0.reshape(vids.shape)
        sel = hit.reshape(vids.shape)
    else:
        idx0 = jnp.zeros_like(idx1)
        sel = jnp.zeros(vids.shape, bool)
    idx = jnp.stack([idx0, idx1]).astype(jnp.int32)
    return vcache, idx, sel.astype(jnp.int32), ok.astype(jnp.int32)


# ---------------------------------------------------------------------------
# Device queues
# ---------------------------------------------------------------------------

class DeviceQueue:
    def __init__(self, capacity: int, width: int, label: str = "queue",
                 query: str = ""):
        self.buf = jnp.full((capacity, width), INVALID, jnp.int32)
        self.n = 0  # host-side authoritative count
        self.capacity = capacity
        self.width = width
        self.label = label   # producing op's label (fault attribution)
        self.query = query   # owning dataflow's query name

    def append(self, rows: jax.Array, m, stats: EngineStats) -> int:
        m_host = int(host_read(stats, "queue_count", m))
        if self.n + max(m_host, rows.shape[0]) > self.capacity:
            # Recoverable pressure, not a crash: the drive()/service recovery
            # ladder restores the last checkpoint at a halved batch (Lemma 5.2
            # slack is a soft bound under degradation).
            raise QueuePressure(
                "queue-overflow",
                f"{self.n}+max({m_host}, {rows.shape[0]}) > {self.capacity} "
                "rows (scheduler slack invariant violated)",
                op=self.label, query=self.query,
            )
        self.buf, _ = ops_mod.queue_append(self.buf, jnp.int32(self.n), rows, m)
        self.n += m_host
        return m_host

    def pop(self, batch: int) -> Tuple[jax.Array, jax.Array]:
        rows, take, _ = ops_mod.queue_pop(self.buf, jnp.int32(self.n), batch)
        self.n -= min(self.n, batch)  # the host already knows ``take``
        return rows, take

    def free(self) -> int:
        return self.capacity - self.n

    def bytes_used(self) -> int:
        return self.n * self.width * 4


# ---------------------------------------------------------------------------
# Operator runtimes
# ---------------------------------------------------------------------------

class _BaseRT:
    label = "op"

    def __init__(self, engine: "HugeEngine", desc: OpDesc, out_q: Optional[DeviceQueue]):
        self.e = engine
        self.desc = desc
        self.out_q = out_q
        self.label = desc.label()
        # Per-session batch size: the recovery ladder restores a session at a
        # halved batch without touching the engine config (queue *pricing*
        # stays at cfg.batch_size, so a degraded session's lease is unchanged).
        self.batch = engine.cfg.batch_size
        self.query = ""  # owning dataflow's query name (fault attribution)

    def output_free(self) -> int:
        return self.out_q.free() if self.out_q is not None else 1 << 62

    def required_slack(self) -> int:
        return 0


class _ScanRT(_BaseRT):
    def __init__(self, engine, desc, out_q):
        super().__init__(engine, desc, out_q)
        self.cursor = 0
        self.delta = desc.scan_epoch == "delta"
        if self.delta:
            if engine.delta_adj is None:
                raise RuntimeError(
                    "delta-seeded scan on an engine with no applied update "
                    "batch — call HugeEngine.apply_updates first"
                )
            self.total = int(engine.delta_total)
        else:
            self.total = int(engine.graph.num_directed_edges)

    def has_input(self) -> bool:
        return self.cursor < self.total

    def required_slack(self) -> int:
        return self.batch

    def run_one(self) -> None:
        e = self.e
        e._inject(("queue-overflow", "shard-loss"), self.label, self.query)
        src = e.delta_src_pad if self.delta else e.src_pad
        dst = e.delta_dst_pad if self.delta else e.dst_pad
        rows, n = ops_mod.scan_batch(
            src, dst, jnp.int32(self.cursor), jnp.int32(self.total),
            self.batch, self.desc.lt_positions, self.desc.gt_positions,
        )
        self.cursor += self.batch
        m = self.out_q.append(rows, n, e.stats)
        e.stats.batches += 1
        e.stats.rows_emitted += m


class _ExtendRT(_BaseRT):
    def __init__(self, engine, desc, in_q, out_q, comm: str):
        super().__init__(engine, desc, out_q)
        self.in_q = in_q
        self.comm = comm

    def has_input(self) -> bool:
        return self.in_q.n > 0

    def required_slack(self) -> int:
        return self.batch * self.e.d_pad

    def run_one(self) -> None:
        e = self.e
        e._inject(("queue-overflow", "shard-loss"), self.label, self.query)
        rows, n = self.in_q.pop(self.batch)
        if self.comm == "pull":
            e.fetch_stage(rows, n, self.desc.ext)
        elif self.comm == "push":
            e.push_wco_stage(rows, n, len(self.desc.ext), rows.shape[1])
        if "old" in self.desc.ext_epochs:
            # Old-epoch positions veto delta membership; the fused kernels
            # know nothing of epochs, so delta extends take the plain path
            # (delta batches are small — this is not the hot loop).
            out, m = ops_mod.delta_extend_batch(
                e.adj, e.delta_adj, rows, n, self.desc.ext,
                tuple(ep == "old" for ep in self.desc.ext_epochs),
                self.desc.lt_positions, self.desc.gt_positions,
                self.batch * e.d_pad,
            )
        elif e.cfg.fused:
            try:
                if e.cfg.faults is not None and e.cfg.faults.should_fire(
                    "kernel-fail", self.label
                ):
                    raise KernelFault("injected fused-kernel failure",
                                      op=self.label, query=self.query)
                tab0, tab1, idx, sel, ok = e._fused_tables(rows, self.desc.ext)
                out, m = ops_mod.fused_extend_batch(
                    tab0, tab1, idx, sel, ok, rows, n,
                    self.desc.lt_positions, self.desc.gt_positions,
                    self.batch * e.d_pad, force_kernel=e.cfg.force_kernel,
                )
            except KernelFault as kf:
                # One-shot graceful degradation: the ref twin is exact, so a
                # failed kernel batch is recomputed unfused instead of failing
                # the query (stat: kernel_fallbacks).
                e.stats.kernel_fallbacks += 1
                _log.warning("fused extend fell back to ref twin: %s", kf)
                out, m = ops_mod.extend_batch(
                    e.adj, rows, n, self.desc.ext, self.desc.lt_positions,
                    self.desc.gt_positions, self.batch * e.d_pad,
                    use_kernel=False,
                )
        else:
            out, m = ops_mod.extend_batch(
                e.adj, rows, n, self.desc.ext, self.desc.lt_positions,
                self.desc.gt_positions, self.batch * e.d_pad,
            )
        cnt = self.out_q.append(out, m, e.stats)
        e.stats.batches += 1
        e.stats.rows_emitted += cnt


class _VerifyRT(_BaseRT):
    def __init__(self, engine, desc, in_q, out_q, comm: str):
        super().__init__(engine, desc, out_q)
        self.in_q = in_q
        self.comm = comm

    def has_input(self) -> bool:
        return self.in_q.n > 0

    def required_slack(self) -> int:
        return self.batch

    def run_one(self) -> None:
        e = self.e
        e._inject(("queue-overflow", "shard-loss"), self.label, self.query)
        rows, n = self.in_q.pop(self.batch)
        if self.comm == "pull":
            e.fetch_stage(rows, n, self.desc.ext)
        if "old" in self.desc.ext_epochs:
            out, m = ops_mod.delta_verify_batch(
                e.adj, e.delta_adj, rows, n, self.desc.ext,
                tuple(ep == "old" for ep in self.desc.ext_epochs),
                self.desc.verify_pos, self.batch,
            )
        elif e.cfg.fused:
            try:
                if e.cfg.faults is not None and e.cfg.faults.should_fire(
                    "kernel-fail", self.label
                ):
                    raise KernelFault("injected fused-kernel failure",
                                      op=self.label, query=self.query)
                tab0, tab1, idx, sel, ok = e._fused_tables(rows, self.desc.ext)
                out, m = ops_mod.fused_verify_batch(
                    tab0, tab1, idx, sel, ok, rows, n, self.desc.verify_pos,
                    self.batch, force_kernel=e.cfg.force_kernel,
                )
            except KernelFault as kf:
                e.stats.kernel_fallbacks += 1
                _log.warning("fused verify fell back to ref twin: %s", kf)
                out, m = ops_mod.verify_batch(
                    e.adj, rows, n, self.desc.ext, self.desc.verify_pos,
                    self.batch,
                )
        else:
            out, m = ops_mod.verify_batch(
                e.adj, rows, n, self.desc.ext, self.desc.verify_pos, self.batch
            )
        cnt = self.out_q.append(out, m, e.stats)
        e.stats.batches += 1
        e.stats.rows_emitted += cnt


class _JoinRT(_BaseRT):
    """PUSH-JOIN: the left input is fully buffered (barrier, §5.4), then the
    right queue is streamed batch-wise against it. The barrier is expressed
    through ``has_input``: the join reports no input until every ancestor of
    its left branch has drained (``left_branch_done``, wired by the engine
    from Dataflow.ancestors), so the generalised AdaptiveScheduler drives
    whole DAGs without per-branch sub-schedulers."""

    def __init__(self, engine, desc, left_q, right_q, out_q):
        super().__init__(engine, desc, out_q)
        self.left_q = left_q
        self.right_q = right_q
        self.shuffle_charged = False
        self._prepared = None  # (sorted_keys, sorted_buf) once left side final
        self.left_branch_done = lambda: True  # installed by the engine

    def has_input(self) -> bool:
        return self.right_q.n > 0 and self.left_branch_done()

    def required_slack(self) -> int:
        return self.e.cfg.join_out_capacity

    def run_one(self) -> None:
        e = self.e
        e._inject(("join-overflow", "shard-loss"), self.label, self.query)
        frac = (e.cfg.num_machines - 1) / max(1, e.cfg.num_machines)
        if not self.shuffle_charged:
            # Left side is complete at the barrier: charge its shuffle once.
            # The right side streams, so it is charged per popped batch below.
            e.stats.pushed_bytes += int(self.left_q.n * self.left_q.width * 4 * frac)
            self.shuffle_charged = True
        if self._prepared is None:
            # The left branch is complete (barrier, §5.4): merge-sort it by key
            # once — the paper's buffered external sort.
            self._prepared = ops_mod.join_prepare(
                self.left_q.buf, jnp.int32(self.left_q.n), self.desc.key_left
            )
        rrows, rn = self.right_q.pop(max(64, self.batch))
        use_kernel = e.cfg.fused
        if use_kernel and e.cfg.faults is not None and e.cfg.faults.should_fire(
            "kernel-fail", self.label
        ):
            # One-shot degradation for the probe's bounds kernel: the binary-
            # search ref path is exact, so the batch recomputes unfused.
            e.stats.kernel_fallbacks += 1
            _log.warning("join probe kernel failed at op=%s; using ref bounds",
                         self.label)
            use_kernel = False
        out, m, overflow = ops_mod.join_probe(
            self._prepared[0], self._prepared[1], rrows, rn,
            self.desc.key_right, self.desc.right_extra,
            self.desc.cross_neq, self.desc.cross_lt, e.cfg.join_out_capacity,
            use_kernel=use_kernel, force_kernel=e.cfg.force_kernel,
        )
        rn, overflow = host_read(e.stats, "join_count", (rn, overflow))
        e.stats.pushed_bytes += int(int(rn) * self.right_q.width * 4 * frac)
        if overflow:
            e.stats.join_overflows += 1
            raise QueuePressure(
                "join-overflow",
                f"probe output exceeded join_out_capacity="
                f"{e.cfg.join_out_capacity} with right batch {int(rn)} "
                "(results would be lost)",
                op=self.label, query=self.query,
            )
        cnt = self.out_q.append(out, m, e.stats)
        e.stats.batches += 1
        e.stats.rows_emitted += cnt


class _SinkRT(_BaseRT):
    def __init__(self, engine, desc, in_q):
        super().__init__(engine, desc, None)
        self.in_q = in_q
        self.rows_out: List[np.ndarray] = []
        # Drain in large fixed-size chunks (one jit signature).
        self.drain = min(in_q.capacity, max(engine.cfg.batch_size * engine.d_pad, 1 << 15))

    def has_input(self) -> bool:
        return self.in_q.n > 0

    def run_one(self) -> None:
        e = self.e
        rows, n = self.in_q.pop(self.drain)
        n_host = int(host_read(e.stats, "sink_count", n))
        e.stats.count += n_host
        if e.cfg.materialize and sum(r.shape[0] for r in self.rows_out) < e.cfg.materialize_cap:
            host = host_read(e.stats, "sink_count",
                             rows[:n_host] if n_host <= rows.shape[0] else rows)
            self.rows_out.append(host[:n_host])
        # Track per-machine result distribution for the load-balance experiment.
        if e.track_balance and n_host:
            host = host_read(e.stats, "sink_count", rows)[:n_host]
            owners = host[:, 0] % e.cfg.num_machines
            np.add.at(e.balance_rows, owners, 1)
        e.stats.batches += 1


# ---------------------------------------------------------------------------
# Multi-tenant building blocks (serve/graph_service.py)
# ---------------------------------------------------------------------------

class QueueSlotPool:
    """Aggregate queue budget shared by every session on one engine.

    Theorem 5.4 bounds a single query's intermediate state by O(|V_q|²·D_G);
    the pool turns that into a *service* invariant: each admitted query leases
    the int32 cells (rows × width) its preallocated queues will occupy, and
    admission fails — queueing the request instead of OOMing the device —
    once the aggregate lease would exceed ``total_cells``. Releases happen
    when a query completes or is cancelled, freeing its slice for the
    admission queue (DESIGN.md §Graph-service)."""

    def __init__(self, total_cells: int):
        self.total_cells = int(total_cells)
        self.leased_cells = 0

    def free_cells(self) -> int:
        return self.total_cells - self.leased_cells

    def try_lease(self, cells: int) -> bool:
        if cells > self.free_cells():
            return False
        self.leased_cells += cells
        return True

    def release(self, cells: int) -> None:
        # Not an assert (stripped under python -O): over-release is pool-
        # accounting corruption — clamp so the pool stays usable, then raise
        # with the offending lease size so the caller is attributable.
        if cells > self.leased_cells:
            leaked = cells - self.leased_cells
            _log.error(
                "queue-slot pool over-release: released %d cells with only %d "
                "leased (%d excess)", cells, self.leased_cells, leaked,
            )
            self.leased_cells = 0
            raise RuntimeError(
                f"queue-slot pool released {cells} cells but only "
                f"{cells - leaked} were leased (over-release of {leaked})"
            )
        self.leased_cells -= cells


class _ScopedRT:
    """OperatorRuntime view that charges its work to one session's stats.

    Sessions from different tenants interleave inside a *single* scheduler
    pass, so per-tenant attribution can't happen at pass granularity: the
    wrapper swaps the engine's stats target around each ``run_one`` (every
    stats mutation — runtimes, fetch_stage, push accounting — goes through
    ``engine.stats``), keeping the underlying runtimes untouched."""

    __slots__ = ("rt", "e", "stats", "label", "session", "span")

    def __init__(self, rt: _BaseRT, engine: "HugeEngine", stats: EngineStats,
                 session: "EngineSession" = None):
        self.rt = rt
        self.e = engine
        self.stats = stats
        self.label = rt.label
        self.session = session
        self.span = f"op.{rt.desc.kind}"

    def has_input(self) -> bool:
        return self.rt.has_input()

    def output_free(self) -> int:
        return self.rt.output_free()

    def required_slack(self) -> int:
        return self.rt.required_slack()

    def run_one(self) -> None:
        prev = self.e.stats
        self.e.stats = self.stats
        try:
            with span(self.span):
                self.rt.run_one()
        except EnumerationFault as f:
            # Attribute the fault to the owning session so a multi-tenant
            # scheduler pass can fail/recover exactly one query.
            f.session = self.session
            raise
        finally:
            self.e.stats = prev


def fault_tolerant_sizing(cfg: EngineConfig) -> bool:
    """Whether queue sizing must include retry slack: true when a fault plan
    is armed *and* the recovery ladder is on (a recovered retry replays a
    checkpointed batch while the original batch may still occupy its queue,
    so each queue needs a second worst-case batch of Lemma 5.2 slack)."""
    return getattr(cfg, "faults", None) is not None and getattr(
        cfg, "recover", False
    )


def _queue_plan(
    flow: Dataflow,
    cfg: EngineConfig,
    d_pad: int,
    queue_capacity: int | None = None,
    join_buffer_capacity: int | None = None,
    fault_tolerant: bool | None = None,
) -> Dict[int, Tuple[int, int]]:
    """Queue sizing for a dataflow: ``{op_index: (physical_rows, width)}``.

    An op feeding a PUSH-JOIN buffers its side fully; every queue carries one
    worst-case batch of slack on top (the Lemma 5.2 overflow allowance — also
    what lets a join feed another join). Fault-tolerant configs (armed fault
    plan + recovery on) double that slack: a post-restore retry can re-append
    a replayed batch on top of rows the original attempt already parked
    (flowcheck rule ``retry-slack`` catches pricing that ignores this).
    Shared by session allocation and by the service's admission check (which
    must price a query *before* paying for it)."""
    qcap = cfg.queue_capacity if queue_capacity is None else queue_capacity
    jcap = cfg.join_buffer_capacity if join_buffer_capacity is None else join_buffer_capacity
    if fault_tolerant is None:
        fault_tolerant = fault_tolerant_sizing(cfg)
    slack_mult = 2 if fault_tolerant else 1
    succ: Dict[int, int] = {}
    for i, op in enumerate(flow.ops):
        for j in op.inputs:
            succ[j] = i
    plan: Dict[int, Tuple[int, int]] = {}
    for i, op in enumerate(flow.ops):
        if op.kind == "sink":
            continue
        slack = {
            "scan": cfg.batch_size,
            "verify": cfg.batch_size,
            "extend": cfg.batch_size * d_pad,
            "join": cfg.join_out_capacity,
        }[op.kind] * slack_mult
        s = succ.get(i)
        if s is not None and flow.ops[s].kind == "join":
            cap = jcap + slack
        else:
            cap = qcap + slack
        plan[i] = (cap, len(op.schema))
    return plan


def flow_queue_cells(
    flow: Dataflow,
    cfg: EngineConfig,
    d_pad: int,
    queue_capacity: int | None = None,
    join_buffer_capacity: int | None = None,
    fault_tolerant: bool | None = None,
) -> int:
    """Total int32 cells a session over ``flow`` will preallocate — the
    quantity a ``QueueSlotPool`` lease is denominated in. ``fault_tolerant``
    defaults to deriving from ``cfg`` (see ``fault_tolerant_sizing``), so
    pricing and allocation always agree."""
    return sum(
        cap * width
        for cap, width in _queue_plan(
            flow, cfg, d_pad, queue_capacity, join_buffer_capacity,
            fault_tolerant,
        ).values()
    )


class EngineSession:
    """One query's execution state on a shared engine: its slot-slice of
    device queues, its operator runtimes (barrier-wired), and its private
    stats. Sessions are driven either to completion (``run``, what
    ``HugeEngine.run`` does) or cooperatively in bounded ticks interleaved
    with other tenants' sessions (``chain`` handed to one shared
    ``AdaptiveScheduler`` per service tick — serve/graph_service.py)."""

    def __init__(
        self,
        engine: "HugeEngine",
        flow: Dataflow,
        stats: EngineStats | None = None,
        queue_capacity: int | None = None,
        join_buffer_capacity: int | None = None,
        batch_size: int | None = None,
        dfs_bias: bool = False,
    ):
        self.engine = engine
        self.flow = flow
        self.stats = stats if stats is not None else EngineStats()
        self.sched_stats = ScheduleStats()
        # Per-session degradation state: a restored session may run a smaller
        # batch with a DFS-biased scheduler while keeping cfg-priced queues
        # (so its QueueSlotPool lease is unchanged).
        self.batch_size = int(batch_size) if batch_size else engine.cfg.batch_size
        self.dfs_bias = dfs_bias
        ops = flow.ops
        plan = _queue_plan(flow, engine.cfg, engine.d_pad,
                           queue_capacity, join_buffer_capacity)
        self.queues: Dict[int, DeviceQueue] = {
            i: DeviceQueue(cap, width, label=ops[i].label(),
                           query=flow.query_name)
            for i, (cap, width) in plan.items()
        }
        self.queue_cells = sum(cap * width for cap, width in plan.values())

        self.runtimes: Dict[int, _BaseRT] = {}
        for i, op in enumerate(ops):
            q = self.queues.get(i)
            if op.kind == "scan":
                self.runtimes[i] = _ScanRT(engine, op, q)
            elif op.kind == "extend":
                self.runtimes[i] = _ExtendRT(
                    engine, op, self.queues[op.inputs[0]], q, op.comm
                )
            elif op.kind == "verify":
                self.runtimes[i] = _VerifyRT(
                    engine, op, self.queues[op.inputs[0]], q, "pull"
                )
            elif op.kind == "join":
                self.runtimes[i] = _JoinRT(
                    engine, op, self.queues[op.inputs[0]],
                    self.queues[op.inputs[1]], q,
                )
            else:
                self.runtimes[i] = _SinkRT(engine, op, self.queues[op.inputs[0]])
        for rt in self.runtimes.values():
            rt.batch = self.batch_size
            rt.query = flow.query_name

        # Join barriers: a PUSH-JOIN may only probe once every ancestor of its
        # left (buffered) input has drained. With the barrier inside each
        # join's has_input, one generalised scheduler pass over the dataflow's
        # topological order executes the whole DAG.
        runtimes = self.runtimes
        for i, op in enumerate(ops):
            if op.kind != "join":
                continue
            branch = (*flow.ancestors(op.inputs[0]), op.inputs[0])

            def make_done(branch=branch):
                def done() -> bool:
                    return not any(runtimes[j].has_input() for j in branch)
                return done

            runtimes[i].left_branch_done = make_done()

        # Topologically ordered, stats-scoped view for shared scheduler passes.
        self.chain = [
            _ScopedRT(self.runtimes[i], engine, self.stats, session=self)
            for i in range(len(ops))
        ]

    # -- introspection -------------------------------------------------------

    def done(self) -> bool:
        """True once every operator has drained (same criterion that ends a
        dedicated scheduler pass, so a finished session never resumes)."""
        return not any(rt.has_input() for rt in self.runtimes.values())

    def rows_in_flight(self) -> int:
        return sum(q.n for q in self.queues.values())

    def bytes_in_flight(self) -> int:
        return sum(q.bytes_used() for q in self.queues.values())

    def memory_probe(self) -> Tuple[int, int]:
        return self.rows_in_flight(), self.bytes_in_flight()

    # -- checkpoint / resume (DESIGN.md §Fault-tolerance) --------------------

    def snapshot(self) -> Dict[str, object]:
        """Host-side capture of the session's complete execution state.

        Taken *between* scheduler steps, queue contents plus the host-side
        cursors (scan position, join shuffle flag, sink rows, stats) are the
        entire state — all device arrays other than queue rows are immutable
        graph data. ``restore`` therefore resumes exactly-once-correct:
        stats roll back to the snapshot, so rows replayed after a restore are
        never double-counted. Shuffle-byte accounting for already-popped join
        batches may be re-charged on replay (counts stay exact; comm stats
        are approximate under recovery)."""
        queues: Dict[int, Tuple[np.ndarray, int]] = {}
        for i, q in self.queues.items():
            rows = (
                np.asarray(q.buf[: q.n]).copy()
                if q.n
                else np.zeros((0, q.width), np.int32)
            )
            queues[i] = (rows, q.n)
        return {
            "query": self.flow.query_name,
            "batch_size": self.batch_size,
            "queues": queues,
            "scan_cursors": {
                i: rt.cursor
                for i, rt in self.runtimes.items()
                if isinstance(rt, _ScanRT)
            },
            "join_charged": {
                i: rt.shuffle_charged
                for i, rt in self.runtimes.items()
                if isinstance(rt, _JoinRT)
            },
            "sink_rows": {
                i: [r.copy() for r in rt.rows_out]
                for i, rt in self.runtimes.items()
                if isinstance(rt, _SinkRT)
            },
            "stats": copy.copy(self.stats),
            "sched_stats": copy.copy(self.sched_stats),
        }

    @classmethod
    def restore(
        cls,
        engine: "HugeEngine",
        flow: Dataflow,
        snap: Dict[str, object],
        *,
        stats: EngineStats | None = None,
        queue_capacity: int | None = None,
        join_buffer_capacity: int | None = None,
        batch_size: int | None = None,
        dfs_bias: bool = False,
    ) -> "EngineSession":
        """Rebuild a session from ``snapshot()``, optionally degraded to a
        smaller ``batch_size`` (the recovery ladder's halving). Queue
        capacities come from the same pricing as a fresh session, so a
        restored query's slot-pool lease is identical to the original's.
        When ``stats`` is supplied (e.g. the ticket's stats object), snapshot
        values are written into it in place so existing references stay
        valid."""
        if snap.get("query") not in ("", None, flow.query_name):
            raise ValueError(
                f"snapshot is for query {snap['query']!r}, not "
                f"{flow.query_name!r}"
            )
        sess = cls(
            engine, flow, stats=stats, queue_capacity=queue_capacity,
            join_buffer_capacity=join_buffer_capacity,
            batch_size=batch_size or snap["batch_size"], dfs_bias=dfs_bias,
        )
        for i, (rows, n) in snap["queues"].items():
            q = sess.queues[i]
            if n > q.capacity:
                raise ValueError(
                    f"snapshot queue {i} holds {n} rows but the restored "
                    f"queue caps at {q.capacity}"
                )
            if n:
                q.buf = q.buf.at[:n].set(jnp.asarray(rows))
            q.n = int(n)
        for i, cur in snap["scan_cursors"].items():
            sess.runtimes[i].cursor = int(cur)
        for i, charged in snap["join_charged"].items():
            sess.runtimes[i].shuffle_charged = bool(charged)
        for i, rows in snap["sink_rows"].items():
            sess.runtimes[i].rows_out = [r.copy() for r in rows]
        sess.stats.__dict__.update(copy.copy(snap["stats"]).__dict__)
        sess.sched_stats.__dict__.update(copy.copy(snap["sched_stats"]).__dict__)
        return sess

    # -- execution -----------------------------------------------------------

    def tick(self, max_steps: int) -> ScheduleStats:
        """Run up to ``max_steps`` operator batches of this session only
        (single-tenant cooperative slice; the multi-tenant service instead
        concatenates several sessions' chains into one pass)."""
        st = AdaptiveScheduler(
            self.chain, memory_probe=self.memory_probe, dfs_bias=self.dfs_bias
        ).run(max_steps)
        self.sched_stats.merge(st)
        return st

    def run(self) -> ScheduleStats:
        st = AdaptiveScheduler(
            self.chain, memory_probe=self.memory_probe, dfs_bias=self.dfs_bias
        ).run()
        self.sched_stats.merge(st)
        return st

    def result(self) -> EnumerationResult:
        self.stats.peak_queue_rows = self.sched_stats.peak_queue_rows
        self.stats.peak_queue_bytes = self.sched_stats.peak_queue_bytes
        # All sinks, not ops[-1]: a merged flow (merge_flows — multi-tenant
        # service, delta unions) has one sink per source flow, and each sink's
        # schema may order the query vertices differently. Materialised rows
        # are permuted into ascending query-vertex column order before
        # concatenation so the result is one coherent [n, |V_q|] table.
        matches = None
        if self.engine.cfg.materialize:
            chunks: List[np.ndarray] = []
            for si in self.flow.sink_indices():
                sink_rt = self.runtimes[si]
                if not (isinstance(sink_rt, _SinkRT) and sink_rt.rows_out):
                    continue
                rows = np.concatenate(sink_rt.rows_out, axis=0)
                schema = self.flow.ops[si].schema
                perm = [schema.index(v) for v in sorted(schema)]
                chunks.append(rows[:, perm])
            if chunks:
                matches = np.concatenate(chunks, axis=0)
        return EnumerationResult(
            count=self.stats.count, stats=self.stats,
            schedule=self.sched_stats, matches=matches,
        )


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

def _edge_scan_arrays(graph: Graph, batch: int) -> Tuple[jax.Array, jax.Array]:
    """Directed edge arrays padded to a batch multiple (scan_batch's contract)."""
    offsets = np.asarray(graph.offsets)
    deg_np = np.diff(offsets)
    src = np.repeat(np.arange(graph.num_vertices, dtype=np.int32), deg_np)
    dst = np.asarray(graph.nbrs, dtype=np.int32)
    pad = (-len(src)) % batch + batch
    return (
        jnp.asarray(np.concatenate([src, np.full(pad, 0, np.int32)])),
        jnp.asarray(np.concatenate([dst, np.full(pad, INVALID, np.int32)])),
    )


class HugeEngine:
    def __init__(self, graph: Graph, cfg: EngineConfig | None = None, track_balance: bool = False):
        self.cfg = cfg or EngineConfig()
        self._load_graph(graph)
        self.stats = EngineStats()
        self.track_balance = track_balance
        self.balance_rows = np.zeros(self.cfg.num_machines, dtype=np.int64)
        self._reset_caches()
        # Delta state (streaming): installed by apply_updates.
        self.delta_adj: Optional[jax.Array] = None
        self.delta_src_pad: Optional[jax.Array] = None
        self.delta_dst_pad: Optional[jax.Array] = None
        self.delta_total: int = 0

    def _load_graph(self, graph: Graph) -> None:
        """(Re)bind every graph-derived array — also the update path's spine."""
        self.graph = graph
        self.adj = jnp.asarray(graph.padded.adj)
        self.deg = jnp.asarray(graph.padded.deg)
        self.d_pad = graph.padded.d_pad
        assert graph.num_vertices * self.cfg.num_machines < 2**31, (
            "machine-id × vertex-id key must fit int32"
        )
        self.src_pad, self.dst_pad = _edge_scan_arrays(graph, self.cfg.batch_size)

    def _reset_caches(self) -> None:
        """Build (or rebuild) the fetch caches from scratch. Called at init
        and after every apply_updates — cached adjacency slabs and hit/miss
        bookkeeping are stale the moment the graph mutates."""
        self._cache = None
        if self.cfg.cache_capacity > 0:
            ways = 1 if self.cfg.cache_policy == "direct" else self.cfg.cache_ways
            self._cache = _make_stacked_cache(
                self.cfg.num_machines, self.cfg.cache_capacity, ways
            )
        # Device-level LRBU *value* cache serving adjacency slabs to the fused
        # kernels (the per-machine caches above are stats-only simulation).
        self._vcache = None
        if self.cfg.fused and self.cfg.cache_capacity > 0:
            self._vcache = lrbu.make_cache(
                self.cfg.cache_capacity, ways=self.cfg.cache_ways, d_pad=self.d_pad
            )

    # -- streaming updates (DESIGN.md §Delta-plans) ----------------------------

    def apply_updates(self, batch: GraphUpdateBatch) -> AppliedUpdates:
        """Apply an edge-insert batch and arm the delta execution state.

        Row-local storage rebuild (graph/storage.apply_updates), then every
        graph-derived array is rebound and both fetch caches are dropped —
        a cached slab from the pre-batch graph would silently corrupt Eq.-2
        intersections. The delta graph (genuinely-new edges only) becomes the
        seed for delta-seeded scans and the old-epoch membership veto."""
        applied = storage_apply_updates(self.graph, batch)
        self._load_graph(applied.graph)
        self._reset_caches()
        delta = applied.delta
        self.delta_adj = jnp.asarray(delta.padded.adj)
        self.delta_src_pad, self.delta_dst_pad = _edge_scan_arrays(
            delta, self.cfg.batch_size
        )
        self.delta_total = int(delta.num_directed_edges)
        return applied

    def run_delta(
        self,
        query_or_plan: QueryGraph | ExecutionPlan,
        space: str = "huge",
        stats: GraphStats | None = None,
    ) -> EnumerationResult:
        """Enumerate only the matches *created* by the last applied batch.

        Executes the delta-join decomposition (dataflow.delta_flows): one
        delta-seeded flow per query edge, merged into a single multi-sink DAG
        so one scheduler pass interleaves all k flows through the standard
        EngineSession/AdaptiveScheduler machinery. Exactly-once: a new match
        is produced by the flow of its minimum-index delta query edge."""
        if self.delta_adj is None:
            raise RuntimeError(
                "run_delta before apply_updates: no delta batch is armed"
            )
        if isinstance(query_or_plan, QueryGraph):
            gstats = stats or GraphStats.from_graph(self.graph)
            plan = optimal_plan(query_or_plan, gstats, self.cfg.num_machines, space)
        elif isinstance(query_or_plan, ExecutionPlan):
            plan = query_or_plan
        else:
            raise TypeError(
                "run_delta needs a QueryGraph or ExecutionPlan (delta flows "
                "are derived from the query, not from an existing Dataflow)"
            )
        t_start = time.perf_counter()
        flows = delta_flows(plan)
        merged, _ = merge_flows(flows)
        session = self.prepare(merged)
        self._queues = session.queues
        session = self.drive(session)
        result = session.result()
        result.stats.wall_time = time.perf_counter() - t_start
        return result

    # -- fetch stage (pull accounting) ---------------------------------------

    def fetch_stage(self, rows: jax.Array, n: jax.Array, ext: Tuple[int, ...]) -> None:
        with span("engine.fetch_stage"):
            cfg = self.cfg
            self._cache, acct = _fetch_accounting(
                self._cache, self.deg, rows, n, tuple(ext), cfg.num_machines,
                self.graph.num_vertices,
                cfg.cache_policy if self._cache is not None else None,
            )
            pulled, hits, misses = (
                int(x) for x in host_read(self.stats, "fetch_accounting", acct))
            self.stats.pulled_bytes += pulled
            self.stats.cache_hits += hits
            self.stats.cache_misses += misses

    # -- fused hot path: value-cache probe prologue ----------------------------

    def _fused_tables(self, rows: jax.Array, ext: Tuple[int, ...]):
        """Build the (tab0, tab1, idx, sel, ok) slab addressing of the fused
        kernels for one batch: insert the batch's deduped vertices into the
        LRBU value cache (seal/release), then probe it — hits read cache slabs
        (tab0), misses fall back to the adjacency table (tab1)."""
        self._vcache, idx, sel, ok = _fused_addressing(
            self._vcache, self.adj, self.deg, rows, tuple(ext),
            self._vcache is not None,
        )
        if self._vcache is not None:
            tab0 = self._vcache.values.reshape(-1, self.d_pad)
        else:
            tab0 = self.adj[:1]
        return tab0, self.adj, idx, sel, ok

    # -- push accounting for wco-push extends (BiGJoin-style plans) -----------

    def push_wco_stage(self, rows: jax.Array, n: jax.Array, n_ext: int, k: int) -> None:
        frac = (self.cfg.num_machines - 1) / max(1, self.cfg.num_machines)
        n = int(host_read(self.stats, "push_count", n))
        self.stats.pushed_bytes += int(n * k * 4 * n_ext * frac)

    # -- memory probe ----------------------------------------------------------

    def _memory_probe(self):
        rows = sum(q.n for q in self._queues.values())
        nbytes = sum(q.bytes_used() for q in self._queues.values())
        return rows, nbytes

    # -- fault injection (core/faults.py) --------------------------------------

    def _inject(self, kinds: Tuple[str, ...], op: str, query: str = "") -> None:
        """Probe the armed FaultPlan at an operator invocation and raise the
        matching structured fault. Host-side only — never reached from traced
        code, so jit caches are fault-agnostic."""
        fp = self.cfg.faults
        if fp is None:
            return
        for kind in kinds:
            if fp.should_fire(kind, op):
                if kind == "shard-loss":
                    raise ShardLoss(fp.seed % self.cfg.num_machines,
                                    op=op, query=query)
                raise QueuePressure(kind, "injected fault", op=op, query=query)

    # -- execution --------------------------------------------------------------

    def to_flow(
        self,
        query_or_plan: QueryGraph | ExecutionPlan | Dataflow,
        space: str = "huge",
        stats: GraphStats | None = None,
    ) -> Dataflow:
        """Resolve a query / plan / dataflow into an executable dataflow."""
        if isinstance(query_or_plan, Dataflow):
            return query_or_plan
        if isinstance(query_or_plan, QueryGraph):
            gstats = stats or GraphStats.from_graph(self.graph)
            plan = optimal_plan(query_or_plan, gstats, self.cfg.num_machines, space)
        else:
            plan = query_or_plan
        return translate(plan)

    def prepare(
        self,
        query_or_plan: QueryGraph | ExecutionPlan | Dataflow,
        space: str = "huge",
        stats: GraphStats | None = None,
        session_stats: EngineStats | None = None,
        queue_capacity: int | None = None,
        join_buffer_capacity: int | None = None,
    ) -> EngineSession:
        """Build an execution session without running it. Multiple sessions
        can coexist on one engine — they share the graph arrays, the fetch
        caches, and the process-global jit cache, while each owns its
        slot-slice of device queues and its own stats (the multi-tenant
        substrate; see serve/graph_service.py)."""
        with span("engine.prepare"):
            flow = self.to_flow(query_or_plan, space, stats)
            # Mandatory pre-flight (DESIGN.md §Static-analysis): a malformed
            # flow must fail here with structured diagnostics, not mid-run on
            # device. Imported lazily — analysis.flowcheck imports
            # core.dataflow, and the repro.core package itself imports this
            # module.
            from repro.analysis.flowcheck import verify_flow

            verify_flow(flow, cfg=self.cfg, d_pad=self.d_pad,
                        queue_capacity=queue_capacity,
                        join_buffer_capacity=join_buffer_capacity)
            return EngineSession(
                self, flow, stats=session_stats,
                queue_capacity=queue_capacity,
                join_buffer_capacity=join_buffer_capacity,
            )

    def drive(self, session: EngineSession) -> EngineSession:
        """Run a session to completion under the graceful-degradation ladder
        (DESIGN.md §Fault-tolerance). On a recoverable fault the last
        checkpoint is restored — at half the batch with a DFS-biased
        scheduler for ``QueuePressure`` (drain before produce), unchanged for
        ``ShardLoss`` (enumeration is deterministic, so replay is exact) —
        and the run retries, up to ``cfg.max_retries`` times and never below
        ``cfg.min_batch_size``. Returns the session holding the final state
        (a *new* object when recovery restored). With ``cfg.recover`` off the
        session runs once and any fault propagates."""
        cfg = self.cfg
        if not cfg.recover:
            session.run()
            return session
        ckpt_steps = cfg.checkpoint_every_steps
        snap = session.snapshot()
        retries = 0
        while True:
            try:
                if ckpt_steps > 0:
                    while not session.done():
                        session.tick(ckpt_steps)
                        snap = session.snapshot()
                else:
                    session.run()
                return session
            except EnumerationFault as f:
                if not f.recoverable or retries >= cfg.max_retries:
                    raise
                retries += 1
                prev_batch = snap["batch_size"]
                if isinstance(f, ShardLoss):
                    new_batch = prev_batch
                else:
                    new_batch = max(prev_batch // 2, cfg.min_batch_size)
                    if new_batch >= prev_batch:
                        raise EnumerationFault(
                            f.kind,
                            "recovery ladder exhausted: batch already at "
                            f"floor {prev_batch} "
                            "(raise queue capacities or min_batch_size)",
                            op=f.op, query=f.query,
                        ) from f
                _log.warning(
                    "recovering from %s (attempt %d/%d): batch %d -> %d",
                    f, retries, cfg.max_retries, prev_batch, new_batch,
                )
                session = EngineSession.restore(
                    self, session.flow, snap, stats=session.stats,
                    batch_size=new_batch,
                    dfs_bias=not isinstance(f, ShardLoss),
                )
                self._queues = session.queues
                # Counters go up *after* the restore rolled stats back to the
                # snapshot, so recovery history survives the rollback.
                session.stats.retries += 1
                if isinstance(f, ShardLoss):
                    session.stats.restarts += 1
                else:
                    session.stats.pressure_events += 1
                snap = session.snapshot()

    def run(
        self,
        query_or_plan: QueryGraph | ExecutionPlan | Dataflow,
        space: str = "huge",
        stats: GraphStats | None = None,
    ) -> EnumerationResult:
        t_start = time.perf_counter()
        session = self.prepare(query_or_plan, space, stats, session_stats=self.stats)
        self._queues = session.queues  # keeps _memory_probe over the live run
        session = self.drive(session)
        result = session.result()
        self.stats.wall_time = time.perf_counter() - t_start
        self.stats.per_machine_rows = self.balance_rows.copy()
        return result


def enumerate_query(
    graph: Graph,
    query: QueryGraph,
    cfg: EngineConfig | None = None,
    space: str = "huge",
) -> EnumerationResult:
    """One-call API: plan, translate, schedule, execute, count."""
    return HugeEngine(graph, cfg).run(query, space=space)
