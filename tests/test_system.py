"""End-to-end behaviour tests: the full HUGE pipeline against the VF2 oracle."""
import pytest

from repro.core import query as Q
from repro.core.engine import EngineConfig, HugeEngine
from repro.graph import erdos_renyi, powerlaw_graph, ring_of_cliques, grid_graph
from repro.graph.oracle import count_instances


def _cfg(**kw):
    base = dict(batch_size=128, queue_capacity=1 << 14, cache_capacity=1 << 10,
                num_machines=4)
    base.update(kw)
    return EngineConfig(**base)


GRAPHS = {
    "er": lambda: erdos_renyi(150, 6.0, seed=1),
    "powerlaw": lambda: powerlaw_graph(200, 6.0, seed=2),
    "cliques": lambda: ring_of_cliques(8, 5),
    "grid": lambda: grid_graph(12, 12),
}


@pytest.mark.parametrize("gname", list(GRAPHS))
@pytest.mark.parametrize("qname", ["q1", "q2", "q3", "q7"])
def test_counts_match_oracle(gname, qname):
    graph = GRAPHS[gname]()
    query = Q.PAPER_QUERIES[qname]
    res = HugeEngine(graph, _cfg()).run(query)
    assert res.count == count_instances(graph, list(query.edges))


@pytest.mark.parametrize("qname", ["q4", "q5", "q6", "q8"])
def test_larger_queries(qname):
    graph = erdos_renyi(120, 7.0, seed=3)
    query = Q.PAPER_QUERIES[qname]
    res = HugeEngine(graph, _cfg()).run(query)
    assert res.count == count_instances(graph, list(query.edges))


@pytest.mark.parametrize("space", ["huge", "bigjoin", "benu", "rads", "seed", "starjoin"])
def test_all_plan_spaces_agree(space):
    """Every Table-2 plan space must produce identical counts (Remark 3.2)."""
    graph = erdos_renyi(120, 6.0, seed=4)
    query = Q.PAPER_QUERIES["q1"]
    res = HugeEngine(graph, _cfg()).run(query, space=space)
    assert res.count == count_instances(graph, list(query.edges))


def test_matches_materialised_exactly():
    """Not just the count: the actual match set equals brute force."""
    from repro.graph.oracle import enumerate_instances_bruteforce

    graph = erdos_renyi(60, 5.0, seed=5)
    query = Q.triangle()
    res = HugeEngine(graph, _cfg(materialize=True)).run(query)
    got = set()
    if res.matches is not None:
        for row in res.matches:
            got.add(frozenset(int(x) for x in row))
    want = enumerate_instances_bruteforce(graph, list(query.edges))
    assert got == want


def test_memory_stays_bounded():
    """Peak queue fill never exceeds capacity + one batch's worst case
    (Theorem 5.4 made structural)."""
    graph = powerlaw_graph(300, 8.0, seed=6)
    cfg = _cfg(queue_capacity=1 << 12, batch_size=128)
    eng = HugeEngine(graph, cfg)
    res = eng.run(Q.PAPER_QUERIES["q1"])
    d_pad = graph.padded.d_pad
    per_queue_cap = cfg.queue_capacity + cfg.batch_size * d_pad
    assert res.stats.peak_queue_rows <= 4 * per_queue_cap  # ≤ #ops × cap
    assert res.count == count_instances(graph, [(0, 1), (1, 2), (2, 3), (3, 0)])


def test_dfs_vs_bfs_same_count():
    graph = erdos_renyi(150, 6.0, seed=7)
    query = Q.PAPER_QUERIES["q2"]
    small = HugeEngine(graph, _cfg(queue_capacity=1 << 10)).run(query)
    big = HugeEngine(graph, _cfg(queue_capacity=1 << 18)).run(query)
    assert small.count == big.count


def test_cache_policies_do_not_change_results():
    graph = powerlaw_graph(200, 6.0, seed=8)
    query = Q.PAPER_QUERIES["q1"]
    counts = set()
    for policy in ("lrbu", "lru", "direct"):
        counts.add(HugeEngine(graph, _cfg(cache_policy=policy)).run(query).count)
    counts.add(HugeEngine(graph, _cfg(cache_capacity=0)).run(query).count)
    assert len(counts) == 1


def test_intersect_kernel_path_agrees():
    """The extend's membership goes through the kernel dispatch
    (kernels/intersect); its counts equal the oracle's."""
    graph = erdos_renyi(100, 5.0, seed=9)
    query = Q.PAPER_QUERIES["q2"]
    res = HugeEngine(graph, _cfg()).run(query)
    assert res.count == count_instances(graph, list(query.edges))
