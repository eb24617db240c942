"""Quickstart: enumerate subgraphs with HUGE in ~20 lines.

    PYTHONPATH=src python examples/quickstart.py
"""
from repro.core.engine import EngineConfig, enumerate_query
from repro.core.query import PAPER_QUERIES, clique
from repro.graph import powerlaw_graph

# 1. A data graph (here: synthetic power-law; swap in your own edge list via
#    repro.graph.from_edge_list).
graph = powerlaw_graph(num_vertices=2048, avg_degree=6.0, seed=0)
print(f"graph: |V|={graph.num_vertices} |E|={graph.num_edges} d_max={graph.max_degree}")

# 2. A query pattern — the paper's q1 (square) and a 4-clique.
for query in (PAPER_QUERIES["q1"], clique(4)):
    # 3. One call: optimiser (Alg. 1) → dataflow (Alg. 2) → BFS/DFS-adaptive
    #    scheduler (Alg. 5) → count, with communication accounting.
    res = enumerate_query(graph, query, EngineConfig(num_machines=8))
    s = res.stats
    print(
        f"{query.name:10s} count={res.count:>10,}  "
        f"T={s.wall_time:.2f}s  "
        f"pulled={s.pulled_bytes / 1e6:.1f}MB pushed={s.pushed_bytes / 1e6:.1f}MB "
        f"cache-hits={s.hit_rate:.0%}  peak-mem={s.peak_queue_bytes / 1e6:.1f}MB"
    )
