"""The engine's own spans and host-read counter (``core/tracing.py``).

One q3/huge query runs on a small graph under the profiler, inside a
``bench.window`` annotation as a benchmark window would be, and the host
spans are read back from the ``.xplane.pb`` the profiler writes. No span is
opened from outside the program here, so every span read back is the
program's own.
"""
from __future__ import annotations

import collections
import glob
import os

import pytest

from repro.core import tracing
from repro.core.engine import EngineConfig, EngineSession, EngineStats, HugeEngine
from repro.core.query import PAPER_QUERIES
from repro.graph import powerlaw_graph


@pytest.fixture(scope="module")
def engine():
    return HugeEngine(powerlaw_graph(1 << 9, 6.0, seed=0),
                      EngineConfig(batch_size=64))


@pytest.fixture(scope="module")
def flow(engine):
    return engine.to_flow(PAPER_QUERIES["q3"], "huge")


def _reads_per_step(op) -> int:
    """Host reads one batch of ``op`` makes, from the engine's code: the
    output queue's count on every append, the fetch accounting of a pulled
    extend or verify, the pushed count of a pushing extend, the join's pop
    count, the sink's drained count (nothing materialised)."""
    if op.kind == "sink":
        return 1
    if op.kind == "join":
        return 2
    return 1 + (op.kind in ("extend", "verify") and op.comm in ("pull", "push"))


@pytest.fixture(scope="module")
def traced(engine, flow, tmp_path_factory):
    """``(result, spans)`` of one warm query run under the profiler; spans
    are ``(name, start_ns, end_ns)`` of the program's names."""
    import jax
    from jax._src.profiler import ProfileData

    engine.run(flow)  # compile outside the trace
    engine.stats = EngineStats()
    out = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(out)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            res = engine.run(flow)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    spans = [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith(("op.", "engine.", "sync."))]
    return res, spans


def test_every_span_is_named_in_spans(traced):
    _, spans = traced
    assert spans
    assert {n for n, _, _ in spans} <= set(tracing.SPANS)


def test_sync_spans_equal_host_syncs(traced):
    res, spans = traced
    syncs = [s for s in spans if s[0].startswith("sync.")]
    assert res.stats.host_syncs > 0
    assert len(syncs) == res.stats.host_syncs


def test_one_op_span_per_step(traced):
    res, spans = traced
    ops = [s for s in spans if s[0].startswith("op.")]
    assert len(ops) == res.schedule.steps
    names = collections.Counter(n for n, _, _ in spans)
    assert names["engine.prepare"] == 1
    assert names["engine.fetch_stage"] == names["sync.fetch_accounting"] > 0


def test_every_read_lies_inside_an_op_span(traced):
    """A device gap during a read is labelled by the read, inside its step."""
    _, spans = traced
    ops = sorted((s, e) for n, s, e in spans if n.startswith("op."))
    for name, s, e in spans:
        if name.startswith("sync."):
            assert any(a <= s and e <= b for a, b in ops), name


def test_host_syncs_follow_the_code(flow, traced):
    res, spans = traced
    kinds = {op.kind: _reads_per_step(op) for op in flow.ops}
    # Every operator of one kind reads alike in this plan (both extends pull).
    assert all(_reads_per_step(op) == kinds[op.kind] for op in flow.ops)
    steps = collections.Counter(n[len("op."):] for n, _, _ in spans
                                if n.startswith("op."))
    assert res.stats.host_syncs == sum(kinds[k] * n for k, n in steps.items())


def test_host_syncs_roll_back_with_snapshot(engine, flow):
    sess = engine.prepare(flow)
    sess.tick(6)
    snap = sess.snapshot()
    at = (sess.stats.host_syncs, sess.stats.rows_emitted, sess.stats.batches)
    assert at[0] > 0
    sess.tick(6)
    assert sess.stats.host_syncs > at[0]
    back = EngineSession.restore(engine, flow, snap)
    assert (back.stats.host_syncs, back.stats.rows_emitted,
            back.stats.batches) == at
