"""Plan-space comparison (the paper's Exp-1/Exp-9 in example form): run the
same query under every prior system's plan space and print the Table-1-style
breakdown.

    PYTHONPATH=src python examples/compare_plans.py --query q1
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.core.cost import GraphStats
from repro.core.engine import EngineConfig, HugeEngine
from repro.core.optimizer import optimal_plan
from repro.core.query import PAPER_QUERIES
from repro.graph import powerlaw_graph


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--query", default="q1", choices=list(PAPER_QUERIES))
    ap.add_argument("--vertices", type=int, default=4096)
    args = ap.parse_args()

    graph = powerlaw_graph(args.vertices, 8.0, seed=7)
    query = PAPER_QUERIES[args.query]
    stats = GraphStats.from_graph(graph)
    print(f"{'system':10s} {'T':>8s} {'C(MB)':>8s} {'M(MB)':>8s} {'count':>10s}")
    for system in ("starjoin", "seed", "bigjoin", "benu", "rads", "huge"):
        plan = optimal_plan(query, stats, 8, system)
        res = HugeEngine(graph, EngineConfig(num_machines=8)).run(plan)
        s = res.stats
        print(
            f"{system:10s} {s.wall_time:8.2f} "
            f"{s.total_comm_bytes / 1e6:8.2f} {s.peak_queue_bytes / 1e6:8.2f} {res.count:>10,}"
        )


if __name__ == "__main__":
    main()
