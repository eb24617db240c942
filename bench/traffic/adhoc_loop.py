"""Ad-hoc enumeration: one client runs whole queries back to back.

Mix keys: ``query`` (a name in the program's ``PAPER_QUERIES``) and ``space``
(the plan space). Configuration keys: the graph (``bench/graphs.py``),
``engine`` (``huge``: one ``HugeEngine`` on the first chip) and
``batch_size``.

Set-up builds the graph, loads it and warms up the query's shapes by
ticking a prepared ``EngineSession``, one batch at a time down the chain,
until every operator has run. The window starts whole
queries, planned by the engine's own planner and run through its ``run``,
until ``--seconds`` have passed, starts none after that, and closes when
the query in flight ends: every query in it is checked whole.
It measures ``throughput``: every match of every query over the window's
wall time.
"""
from __future__ import annotations

import time

from bench import graphs, reference


class _Seen:
    """A scheduler-facing view of one operator that notes that it ran."""

    def __init__(self, rt):
        self.rt, self.ran, self.label = rt, False, rt.label

    def has_input(self):
        return self.rt.has_input()

    def output_free(self):
        return self.rt.output_free()

    def required_slack(self):
        return self.rt.required_slack()

    def run_one(self):
        self.rt.run_one()
        self.ran = True


def _op_span(rt) -> str:
    """``op.<kind>`` of an operator runtime (through the session's scoping
    view where there is one)."""
    desc = getattr(getattr(rt, "rt", rt), "desc", None)
    return f"op.{getattr(desc, 'kind', 'unknown')}"


def _build(ctx):
    from repro.graph import build_graph

    cfg = ctx.config
    with ctx.phase("graph_build"):
        g = build_graph(graphs.config_edges(cfg, ctx.seed), int(cfg["vertices"]))
    if cfg["engine"] != "huge":
        raise ValueError(f"unknown engine {cfg['engine']!r}")
    with ctx.phase("device_load"):
        from repro.core.engine import EngineConfig, HugeEngine

        eng = HugeEngine(g, EngineConfig(batch_size=int(cfg["batch_size"])))
        eng.adj.block_until_ready()
    ctx.log(f"graph: {g.num_vertices} vertices, {g.num_edges} edges, "
            f"d_pad={g.padded.d_pad}, engine={cfg['engine']}")
    return g, eng


def _instrument(ctx) -> None:
    from repro.core import engine as mod

    ctx.wrap_spans(mod._ScopedRT, "run_one", _op_span)
    ctx.wrap_spans(mod.HugeEngine, "fetch_stage", lambda _: "engine.fetch_stage")
    ctx.wrap_spans(mod.EngineSession, "tick", lambda _: "engine.session_tick")


def setup(ctx):
    from repro.core.query import PAPER_QUERIES

    g, eng = _build(ctx)
    query, space = PAPER_QUERIES[ctx.mix["query"]], ctx.mix["space"]
    _instrument(ctx)
    with ctx.phase("warm_up"):
        session = eng.prepare(eng.to_flow(query, space))
        session.chain = chain = [_Seen(rt) for rt in session.chain]
        # One batch at a time down the chain (the scheduler's own DFS
        # mode): every operator runs within a few steps, where the
        # default schedule reaches the sink only as the query ends.
        session.dfs_bias = True
        ticks = 0
        while not all(rt.ran for rt in chain):
            if session.done():
                raise RuntimeError("warm-up drained before every operator ran")
            session.tick(4)
            ticks += 1
        ctx.log(f"warm-up: {ticks} ticks of 4 steps ran all "
                f"{len(chain)} operators")
        del session
    return {"graph": g, "engine": eng, "query": query, "space": space}


def _run_one(state, flow) -> dict:
    from repro.core.engine import EngineStats

    eng = state["engine"]
    eng.stats = EngineStats()
    res = eng.run(flow)
    st = res.stats
    return {"count": int(res.count), "rows_emitted": int(st.rows_emitted),
            "steps": int(res.schedule.steps),
            "kernel_fallbacks": int(st.kernel_fallbacks),
            "retries": int(st.retries)}


def window(ctx, state) -> dict:
    t0 = time.perf_counter()
    end = t0 + ctx.seconds
    runs = []
    while True:
        with ctx.span("planner.to_flow"):
            flow = state["engine"].to_flow(state["query"], state["space"])
        with ctx.span("engine.run"):
            runs.append(_run_one(state, flow))
        if time.perf_counter() >= end:
            break
    window_s = time.perf_counter() - t0
    matches = sum(r["count"] for r in runs)
    counters = {k: sum(r[k] for r in runs) for k in runs[0]}
    counters.update(queries=len(runs), matches=matches)
    ctx.log(f"window: {len(runs)} queries, {matches} matches in {window_s:.3f}s "
            f"({matches / window_s:.3f} matches/s); per query "
            f"{[r['count'] for r in runs]}; counters {counters}")
    return {"window_s": window_s, "attempted": len(runs), "failed": 0,
            "measures": {"throughput": matches / window_s},
            "counters": counters, "answers": [r["count"] for r in runs]}


def expected(ctx) -> int:
    """The reference's count of the mix's query on this run's graph."""
    n = int(ctx.config["vertices"])
    offsets, nbrs = graphs.csr(graphs.config_edges(ctx.config, ctx.seed), n)
    return reference.counts(offsets, nbrs, [ctx.mix["query"]])[ctx.mix["query"]]


def check(ctx, out) -> list:
    """``count_gap``: the largest distance of a query's count from the
    reference's; an exact count, so its limit is 0."""
    want = expected(ctx)
    gap = max(abs(c - want) for c in out["answers"])
    out["failed"] = sum(c != want for c in out["answers"])
    ctx.log(f"reference count {want}; answers {out['answers']}")
    return [("count_gap", gap, 0)]


def control(ctx) -> dict:
    """The reference in the program's place with exactness broken: one
    query answered by the approximate (edge-sampled, scaled) count."""
    n = int(ctx.config["vertices"])
    offsets, nbrs = graphs.csr(graphs.config_edges(ctx.config, ctx.seed), n)
    q = ctx.mix["query"]
    est = reference.approximate_counts(offsets, nbrs, [q], ctx.seed)[q]
    return {"answers": [est], "attempted": 1, "failed": 0}
