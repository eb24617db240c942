"""Chaos matrix: injected faults across engine + distributed + service.

Every leg arms a deterministic :class:`FaultPlan` (seed taken from
``REPRO_FAULT_SEED`` so the CI chaos job sweeps trigger points without
losing replayability) and asserts the acceptance bar from
DESIGN.md §Fault-tolerance: a run under injection either

* **recovers** — final count identical to the fault-free oracle, with the
  recovery visible in the stats (``pressure_events`` / ``restarts`` /
  ``kernel_fallbacks``); or
* **fails structurally** — an :class:`EnumerationFault` carrying kind / op /
  query attribution, with *zero* leaked pool cells or tenant inflight slots.

The "huge"-space q1–q3 plans contain no PUSH-JOINs, so join-overflow legs
run the same queries in the join-only ``"starjoin"`` space.
"""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.core.engine import EngineConfig, EngineSession, HugeEngine
from repro.core.faults import (
    FAULT_KINDS,
    EnumerationFault,
    FaultPlan,
    FaultSpec,
    QueuePressure,
)
from repro.core.query import PAPER_QUERIES
from repro.graph import powerlaw_graph
from repro.graph.oracle import count_instances
from repro.serve.graph_service import (
    DONE,
    FAILED,
    TIMED_OUT,
    GraphQueryRequest,
    GraphService,
    ServiceConfig,
)

SEED = int(os.environ.get("REPRO_FAULT_SEED", "0"))
QUERIES = ("q1", "q2", "q3")


@pytest.fixture(scope="module")
def graph():
    return powerlaw_graph(256, 5.0, seed=3)


@pytest.fixture(scope="module")
def oracle(graph):
    cache = {}

    def _oracle(qname):
        if qname not in cache:
            cache[qname] = count_instances(
                graph, list(PAPER_QUERIES[qname].edges))
        return cache[qname]

    return _oracle


def _plan(kind, op="*", at_step=None):
    return FaultPlan.single(kind, op=op, at_step=at_step, seed=SEED)


def engine_cfg(**kw):
    base = dict(batch_size=128, queue_capacity=1 << 14,
                join_buffer_capacity=1 << 16)
    base.update(kw)
    return EngineConfig(**base)


def svc_cfg(**kw):
    base = dict(queue_capacity=1 << 10, join_buffer_capacity=1 << 12,
                tick_steps=16, max_active=4)
    base.update(kw)
    return ServiceConfig(**base)


# ---------------------------------------------------------------------------
# fault plan determinism
# ---------------------------------------------------------------------------

def test_fault_plan_is_deterministic_and_seed_sensitive():
    a = FaultPlan.single("queue-overflow", seed=SEED)
    b = FaultPlan.single("queue-overflow", seed=SEED)
    fires_a = [a.should_fire("queue-overflow", "SCAN(0, 1)") for _ in range(10)]
    fires_b = [b.should_fire("queue-overflow", "SCAN(0, 1)") for _ in range(10)]
    assert fires_a == fires_b and sum(fires_a) == 1  # one-shot, same step
    a.reset()
    assert [a.should_fire("queue-overflow", "SCAN(0, 1)")
            for _ in range(10)] == fires_a


def test_fault_plan_env_and_validation(monkeypatch):
    assert FaultPlan.from_env({}) is None
    fp = FaultPlan.from_env({"REPRO_FAULT_KIND": "shard-loss",
                             "REPRO_FAULT_SEED": "7",
                             "REPRO_FAULT_OP": "scan",
                             "REPRO_FAULT_STEP": "2"})
    assert fp.seed == 7 and fp.specs[0] == FaultSpec("shard-loss", "scan", 2)
    with pytest.raises(ValueError):
        FaultSpec("not-a-kind")


# ---------------------------------------------------------------------------
# single-process engine: recovery ladder
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("qname", QUERIES)
def test_engine_recovers_queue_overflow(graph, oracle, qname):
    fp = _plan("queue-overflow", at_step=SEED % 3)
    eng = HugeEngine(graph, engine_cfg(faults=fp, recover=True))
    res = eng.run(PAPER_QUERIES[qname])
    assert fp.fired_count("queue-overflow") == 1
    assert res.count == oracle(qname), qname
    assert res.stats.pressure_events >= 1 and res.stats.retries >= 1


@pytest.mark.parametrize("qname", QUERIES)
def test_engine_recovers_shard_loss(graph, oracle, qname):
    fp = _plan("shard-loss", at_step=SEED % 3)
    eng = HugeEngine(graph, engine_cfg(faults=fp, recover=True))
    res = eng.run(PAPER_QUERIES[qname])
    assert fp.fired_count("shard-loss") == 1
    assert res.count == oracle(qname), qname
    assert res.stats.restarts >= 1


@pytest.mark.parametrize("qname", QUERIES)
def test_engine_kernel_fail_falls_back_to_ref(graph, oracle, qname):
    fp = _plan("kernel-fail", at_step=SEED % 2)
    eng = HugeEngine(graph, engine_cfg(faults=fp, fused=True, recover=True))
    res = eng.run(PAPER_QUERIES[qname])
    assert fp.fired_count("kernel-fail") == 1
    assert res.count == oracle(qname), qname
    assert res.stats.kernel_fallbacks >= 1
    assert res.stats.retries == 0  # one-shot fallback, not a restart


@pytest.mark.parametrize("qname", QUERIES)
def test_engine_recovers_join_overflow_starjoin(graph, oracle, qname):
    fp = _plan("join-overflow", at_step=SEED % 2)
    eng = HugeEngine(graph, engine_cfg(faults=fp, recover=True))
    res = eng.run(PAPER_QUERIES[qname], space="starjoin")
    assert fp.fired_count("join-overflow") == 1
    assert res.count == oracle(qname), qname
    assert res.stats.pressure_events >= 1


def test_engine_fault_is_structured_when_recovery_disabled(graph):
    fp = _plan("queue-overflow", at_step=0)
    eng = HugeEngine(graph, engine_cfg(faults=fp, recover=False))
    with pytest.raises(QueuePressure) as ei:
        eng.run(PAPER_QUERIES["q1"])
    f = ei.value
    assert f.kind == "queue-overflow" and f.recoverable
    assert f.op != "?" and f.query == "square"  # attributable


def test_engine_ladder_exhaustion_escalates(graph):
    # Fault re-fires on every attempt; the batch floor equals the starting
    # batch, so the very first halving attempt must escalate structurally.
    fp = FaultPlan.single("queue-overflow", at_step=0, times=100, seed=SEED)
    eng = HugeEngine(graph, engine_cfg(
        batch_size=64, min_batch_size=64, faults=fp, recover=True))
    with pytest.raises(EnumerationFault) as ei:
        eng.run(PAPER_QUERIES["q1"])
    assert "recovery ladder exhausted" in str(ei.value)
    assert not ei.value.recoverable


def test_organic_queue_overflow_is_recoverable_pressure():
    # No injection: a real capacity breach raises attributable QueuePressure
    # (recoverable), not a bare crash. End-to-end the scheduler's Lemma-5.2
    # slack gating prevents this state; the queue itself stays defensive.
    import jax.numpy as jnp

    from repro.core.engine import DeviceQueue, EngineStats

    q = DeviceQueue(capacity=100, width=2, label="EXT(v2)", query="q1")
    with pytest.raises(QueuePressure) as ei:
        q.append(jnp.zeros((128, 2), jnp.int32), jnp.int32(128), EngineStats())
    f = ei.value
    assert f.kind == "queue-overflow" and f.recoverable
    assert f.op == "EXT(v2)" and f.query == "q1"


# ---------------------------------------------------------------------------
# checkpoint / resume (exactly-once)
# ---------------------------------------------------------------------------

def test_snapshot_restore_resumes_exactly_once(graph, oracle):
    eng = HugeEngine(graph, engine_cfg())
    sess = eng.prepare(PAPER_QUERIES["q2"])
    while not sess.done() and sess.stats.count == 0:
        sess.tick(4)
    snap = sess.snapshot()
    mid_count = snap["stats"].count
    # "crash": abandon the session, restore into a brand-new one
    resumed = EngineSession.restore(eng, sess.flow, snap)
    assert resumed.stats.count == mid_count  # rollback to the checkpoint
    resumed.run()
    assert resumed.result().count == oracle("q2")


def test_periodic_checkpoints_bound_replay(graph, oracle):
    fp = _plan("queue-overflow", op="ext", at_step=10)
    eng = HugeEngine(graph, engine_cfg(
        faults=fp, recover=True, checkpoint_every_steps=2))
    res = eng.run(PAPER_QUERIES["q1"])
    assert res.count == oracle("q1")
    assert res.stats.pressure_events >= 1


# ---------------------------------------------------------------------------
# service: admission faults, retry/backoff, deadlines, lease hygiene
# ---------------------------------------------------------------------------

def test_service_lease_oom_is_transient(graph, oracle):
    fp = _plan("lease-oom", op="admit", at_step=0)
    svc = GraphService(graph, svc_cfg(faults=fp))
    t = svc.submit(GraphQueryRequest(tenant="a", query="q1"))
    svc.run_until_idle()
    assert t.status == DONE and t.count == oracle("q1")
    assert any("lease-oom" in f for f in t.failures)
    assert svc.pool.leased_cells == 0


def test_service_crash_releases_lease_and_inflight(graph):
    """Satellite 4: a query crashing mid-run must return the pool to its
    pre-admission state and free the tenant's inflight slot."""
    ecfg = engine_cfg(
        faults=_plan("queue-overflow", op="scan", at_step=1), recover=True)
    svc = GraphService(graph, svc_cfg(max_retries=0), engine_cfg=ecfg)
    pre_cells = svc.pool.leased_cells
    pre_inflight = svc.tenant_usage("a")["inflight"]
    t = svc.submit(GraphQueryRequest(tenant="a", query="q1"))
    svc.run_until_idle()
    assert t.status == FAILED
    assert "queue-overflow" in t.error and t.failures
    assert svc.pool.leased_cells == pre_cells
    assert svc.tenant_usage("a") == {"inflight": pre_inflight,
                                     "queue_cells": 0}
    assert not svc.active and not svc.admission


def test_service_retries_with_backoff_and_succeeds(graph, oracle):
    ecfg = engine_cfg(
        faults=_plan("queue-overflow", op="scan", at_step=1), recover=True)
    svc = GraphService(graph, svc_cfg(max_retries=2, retry_backoff_ticks=1),
                       engine_cfg=ecfg)
    t = svc.submit(GraphQueryRequest(tenant="a", query="q1"))
    svc.run_until_idle()
    assert t.status == DONE and t.count == oracle("q1")
    assert t.attempts == 2 and len(t.failures) == 1
    assert svc.pool.leased_cells == 0


def test_service_checkpoint_degrades_in_place(graph, oracle):
    ecfg = engine_cfg(faults=_plan("queue-overflow", op="ext", at_step=6),
                      recover=True)
    svc = GraphService(graph, svc_cfg(checkpoint_every_ticks=1, tick_steps=4),
                       engine_cfg=ecfg)
    t = svc.submit(GraphQueryRequest(tenant="a", query="q1"))
    svc.run_until_idle()
    assert t.status == DONE and t.count == oracle("q1")
    assert t.attempts == 1              # degraded in place, never requeued
    assert t.stats.pressure_events >= 1
    assert svc.pool.leased_cells == 0


def test_service_deadline_times_out(graph):
    svc = GraphService(graph, svc_cfg())
    t = svc.submit(GraphQueryRequest(tenant="a", query="q1", deadline_s=0.0))
    svc.run_until_idle()
    assert t.status == TIMED_OUT and t.error
    assert svc.pool.leased_cells == 0
    assert svc.tenant_usage("a")["inflight"] == 0


def test_service_snapshot_restore_resumes_running_and_standing(graph, oracle):
    svc = GraphService(graph, svc_cfg(checkpoint_every_ticks=1, tick_steps=4))
    sq = svc.register_standing("s", "q2")
    sq.total_count = 41  # accumulated by (pretend) earlier batches
    svc.submit(GraphQueryRequest(tenant="a", query="q1"))
    for _ in range(6):
        svc.tick()
    assert svc.active, "query must still be mid-flight for the crash test"
    snap = svc.snapshot()
    assert snap["running"] and snap["standing"]
    # simulated crash: rebuild the whole service from the snapshot
    svc2 = GraphService.restore(graph, snap,
                                svc_cfg(checkpoint_every_ticks=1))
    assert svc2.standing[0].total_count == 41
    svc2.run_until_idle()
    assert svc2.pool.leased_cells == 0
    # exactly-once: resume via the public API with a tracked ticket
    svc3 = GraphService(graph, svc_cfg())
    req, flow, sess_snap = snap["running"][0]
    t = svc3.resume(req, flow, sess_snap)
    svc3.run_until_idle()
    assert t.status == DONE and t.count == oracle("q1")


def test_queue_slot_pool_over_release_is_an_error(graph):
    from repro.core.engine import QueueSlotPool

    pool = QueueSlotPool(1000)
    assert pool.try_lease(100)
    with pytest.raises(RuntimeError, match="over-release"):
        pool.release(200)
    assert pool.leased_cells == 0  # clamped, not negative


# ---------------------------------------------------------------------------
# distributed engine (fresh interpreter: XLA device count must precede jax)
# ---------------------------------------------------------------------------

def _run_py(code, timeout=540, devices=4):
    env = dict(os.environ, PYTHONPATH="src",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    r = subprocess.run([sys.executable, "-u", "-c", textwrap.dedent(code)],
                       env=env, cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))),
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_distributed_chaos_matrix():
    """All four engine-level fault kinds on the 4-shard SPMD engine: each
    must recover (restart or degraded batch) to the oracle count."""
    out = _run_py(f"""
        import jax
        from repro.launch.mesh import auto_mesh
        from repro.core import query as Q
        from repro.core.distributed import DistributedEngine, DistConfig
        from repro.core.faults import FaultPlan
        from repro.graph import powerlaw_graph
        from repro.graph.oracle import count_instances

        SEED = {SEED}
        mesh = auto_mesh((4,), ("shards",))
        g = powerlaw_graph(220, 5.0, seed=4)
        q = Q.PAPER_QUERIES["q1"]
        oracle = count_instances(g, list(q.edges))

        def run(kind, op="*", at_step=None, space="huge", fused=False):
            fp = FaultPlan.single(kind, op=op, at_step=at_step, seed=SEED)
            cfg = DistConfig(batch_size=128, queue_capacity=1 << 14,
                             faults=fp, recover=True, fused=fused)
            eng = DistributedEngine(g, mesh, cfg)
            count, stats = eng.run(q, space=space)
            return fp, count, stats

        fp, count, stats = run("queue-overflow", at_step=SEED % 3)
        assert fp.fired_count() == 1 and count == oracle, (count, oracle)
        assert stats["retries"] >= 1 and stats["pressure_events"] >= 1
        print("queue-overflow ok", count)

        fp, count, stats = run("shard-loss", at_step=SEED % 3)
        assert fp.fired_count() == 1 and count == oracle, (count, oracle)
        assert stats["restarts"] >= 1
        print("shard-loss ok", count)

        fp, count, stats = run("kernel-fail", at_step=SEED % 2, fused=True)
        assert fp.fired_count() == 1 and count == oracle, (count, oracle)
        assert stats["kernel_fallbacks"] >= 1
        print("kernel-fail ok", count)

        oracle3 = count_instances(g, list(Q.PAPER_QUERIES["q3"].edges))
        fp = FaultPlan.single("join-overflow", at_step=SEED % 2, seed=SEED)
        cfg = DistConfig(batch_size=128, queue_capacity=1 << 14,
                         join_out_capacity=1 << 18, faults=fp, recover=True)
        eng = DistributedEngine(g, mesh, cfg)
        count, stats = eng.run(Q.PAPER_QUERIES["q3"], space="starjoin")
        assert fp.fired_count() == 1 and count == oracle3, (count, oracle3)
        assert stats["pressure_events"] >= 1
        print("join-overflow ok", count)
    """)
    assert out.count("ok") == 4
