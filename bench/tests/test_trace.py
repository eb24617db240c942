"""The trace reduction on a small synthetic trace with known answers, and on
a trace the profiler records here."""
from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

from bench import trace


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _profile():
    """Window [1000, 11000) ns. Device 0 runs two overlapping ops, a kernel
    and an all-to-all; device 1 one op. The host is in ``engine.run`` for
    [1000, 6000) and in ``service.tick`` for [6000, 11000), with an
    ``op.extend`` span nested in the first."""
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[
            _ev("bench.window", 1000, 10000),
            _ev("engine.run", 1000, 5000),
            _ev("op.extend", 2000, 1000),
            _ev("service.tick", 6000, 5000),
            _ev("$ignored python frame", 1000, 9000),
        ]),
    ])
    dev0 = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[
            _ev("fusion.1", 500, 1500),                 # [500, 2000): 1000 in window
            _ev("fusion.2", 1500, 1000),                # overlaps: union [500, 2500)
            _ev("multiway_membership_kernel.3", 3000, 1000),
            _ev("all-to-all.7", 7000, 2000),
        ]),
        NS(name="XLA Modules", events=[
            _ev("jit_extend_batch(1)", 500, 3500),
            _ev("jit_step(2)", 7000, 2000),
        ]),
    ])
    dev1 = NS(name="/device:TPU:1", lines=[
        NS(name="XLA Ops", events=[_ev("all-to-all.7", 7000, 3000)]),
    ])
    other = NS(name="/device:TPU:0 SparseCore", lines=[
        NS(name="XLA Ops", events=[_ev("noise", 1000, 10000)])])
    return NS(planes=[host, dev0, dev1, other])


@pytest.fixture
def summary():
    return trace.summarize(_profile(), ["engine.run", "op.extend", "service.tick"])


def test_window_and_devices(summary):
    assert summary.window == (1000, 11000)
    assert summary.window_s == pytest.approx(1e-5)
    assert [d.name for d in summary.devices] == ["/device:TPU:0", "/device:TPU:1"]


def test_busy_is_a_union_clipped_to_the_window(summary):
    # dev0: [1000, 2500) + [3000, 4000) + [7000, 9000) = 4500 ns;
    # dev1: [7000, 10000) = 3000 ns; mean 3750 ns.
    assert summary.busy_s() == pytest.approx(3750e-9)
    assert summary.idle_share() == pytest.approx(1 - 3750 / 10000)


def test_kernel_and_collective_time(summary):
    assert summary.op_seconds(r"membership_kernel") == pytest.approx(500e-9)
    assert summary.op_seconds(r"all-to-all") == pytest.approx(2500e-9)
    assert summary.op_share(r"all-to-all") == pytest.approx(0.25)
    assert summary.op_share(r"no_such_kernel") is None


def test_idle_gaps_are_labelled_by_the_innermost_span(summary):
    gaps = summary.idle_gaps()
    # dev0 idle: [2500, 3000) in op.extend, [4000, 7000) mid 5500 in
    # engine.run, [9000, 11000) in service.tick; dev1 idle: [1000, 7000)
    # mid 4000 in engine.run, [10000, 11000) in service.tick.
    assert gaps["op.extend"] == pytest.approx(500e-9 / 2)
    assert gaps["engine.run"] == pytest.approx((3000 + 6000) * 1e-9 / 2)
    assert gaps["service.tick"] == pytest.approx((2000 + 1000) * 1e-9 / 2)
    assert sum(gaps.values()) == pytest.approx(summary.window_s - summary.busy_s())


def test_top_programs_by_module(summary):
    top = dict(summary.top_programs())
    assert top["jit_extend_batch(1)"] == pytest.approx(3000e-9 / 2)
    assert top["jit_step(2)"] == pytest.approx(2000e-9 / 2)


def test_union_merges_overlaps_and_drops_empty():
    assert trace.union([(5, 7), (1, 3), (2, 4), (8, 8)]) == [(1, 4), (5, 7)]


def test_unknown_device_kind_is_an_error():
    assert trace.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        trace.peaks("TPU v99")


def test_missing_window_is_an_error():
    p = _profile()
    p.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError, match="bench.window"):
        trace.summarize(p, [])


def test_recorded_trace(tmp_path):
    """A trace the profiler writes here: the window and the spans are read
    from the host plane (this CPU run has no TPU plane, so no device)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("engine.run"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    s = trace.load(str(tmp_path), ["engine.run"])
    assert s.window_s > 0 and s.devices == []
    assert [sp.name for sp in s.spans].count("engine.run") == 1
    assert s.idle_share() is None
