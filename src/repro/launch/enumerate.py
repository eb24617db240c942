"""Subgraph-enumeration driver (the paper's own workload).

``python -m repro.launch.enumerate --query q1 --vertices 4096 --machines 8``
runs the full HUGE pipeline: optimiser → dataflow → BFS/DFS-adaptive
scheduler → count, with Table-1-style communication/memory accounting.
"""
from __future__ import annotations

import argparse

from repro.configs.huge_enum import EnumConfig
from repro.core.engine import EngineConfig, HugeEngine
from repro.core.optimizer import optimal_plan
from repro.core.cost import GraphStats
from repro.core.dataflow import translate
from repro.core.query import PAPER_QUERIES
from repro.graph import powerlaw_graph
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--query", default="q1", choices=list(PAPER_QUERIES))
    ap.add_argument("--vertices", type=int, default=1 << 13)
    ap.add_argument("--avg-degree", type=float, default=8.0)
    ap.add_argument("--machines", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=1024)
    ap.add_argument("--queue-capacity", type=int, default=1 << 18)
    ap.add_argument("--cache-capacity", type=int, default=1 << 14)
    ap.add_argument("--space", default="huge",
                    choices=["huge", "bigjoin", "benu", "rads", "seed", "starjoin"])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--verify", action="store_true", help="check against networkx")
    args = ap.parse_args(argv)
    enable_compile_cache()

    graph = powerlaw_graph(args.vertices, args.avg_degree, seed=args.seed)
    query = PAPER_QUERIES[args.query]
    plan = optimal_plan(query, GraphStats.from_graph(graph), args.machines, args.space)
    print(plan.describe())
    flow = translate(plan)
    print(flow.describe())

    cfg = EngineConfig(
        batch_size=args.batch_size,
        queue_capacity=args.queue_capacity,
        cache_capacity=args.cache_capacity,
        num_machines=args.machines,
    )
    engine = HugeEngine(graph, cfg)
    res = engine.run(flow)
    s = res.stats
    print(
        f"\n[enumerate] {args.query} on |V|={args.vertices} (space={args.space}): "
        f"count={res.count}\n"
        f"  T={s.wall_time:.2f}s\n"
        f"  C: pulled={s.pulled_bytes / 1e6:.2f}MB pushed={s.pushed_bytes / 1e6:.2f}MB "
        f"cache-hit-rate={s.hit_rate:.2%}\n"
        f"  M: peak queue {s.peak_queue_bytes / 1e6:.2f}MB ({s.peak_queue_rows} rows)"
    )
    if args.verify:
        from repro.graph.oracle import count_instances
        oracle = count_instances(graph, list(query.edges))
        print(f"  oracle={oracle}  MATCH={oracle == res.count}")
        assert oracle == res.count
    return res.count


if __name__ == "__main__":
    main()
