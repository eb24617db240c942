"""The benchmark's data: the copied sampler agrees with the program's
original, the BTER layout keeps the degrees and adds clustering, the
configuration's recorded statistics are those of the graph it makes, and the
host counts agree with networkx."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from bench import graphs, reference

ROOT = Path(graphs.__file__).resolve().parents[1]
SMALL = {"vertices": 1 << 10, "avg_degree": 6.0, "exponent": 2.468,
         "graph_seed": 3, "clustering_max": 0.58, "clustering_decay": 0.012}


def _spec_configs():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return [json.loads((ROOT / c["file"]).read_text()) for c in spec["configs"]]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_sampler_matches_program_generator(seed):
    from repro.graph import build_graph, powerlaw_graph

    n = 1 << 10
    ours = build_graph(graphs.chung_lu_edges(n, 9.87, 2.5, seed), n)
    theirs = powerlaw_graph(n, 9.87, seed=seed)
    np.testing.assert_array_equal(ours.offsets, theirs.offsets)
    np.testing.assert_array_equal(ours.nbrs, theirs.nbrs)


def test_seed_relabels_the_same_graph():
    """Every seed runs the same work: vertices are numbered by degree, the
    counts stay, the order of equal degrees moves; the reference CSR equals
    the program's."""
    from repro.graph import build_graph

    n = SMALL["vertices"]
    a, b = graphs.config_edges(SMALL, 1), graphs.config_edges(SMALL, 2)
    assert not np.array_equal(a, b)
    ca, cb = graphs.csr(a, n), graphs.csr(b, n)
    assert np.all(np.diff(np.diff(ca[0])) >= 0)  # numbered by degree
    np.testing.assert_array_equal(np.diff(ca[0]), np.diff(cb[0]))
    assert reference.counts(*ca, ["q1", "q2", "q3"]) == reference.counts(
        *cb, ["q1", "q2", "q3"])
    g = build_graph(a, n)
    np.testing.assert_array_equal(g.offsets, ca[0])
    np.testing.assert_array_equal(g.nbrs, ca[1])


def test_bter_keeps_degrees_and_adds_clustering():
    """The layout keeps the Chung-Lu draw's degrees up to the pairs drawn
    twice (under a tenth of the degree at 2^12), and has far more triangles
    than the draw itself."""
    n = 1 << 12
    cfg = dict(SMALL, vertices=n, avg_degree=9.87)
    plain = graphs.csr(graphs.chung_lu_edges(n, 9.87, 2.468, 3), n)
    bter = graphs.csr(graphs.structure_edges(cfg), n)
    want, got = np.diff(plain[0]), np.diff(bter[0])
    assert abs(got.mean() / want.mean() - 1) < 0.1
    assert np.corrcoef(want, got)[0, 1] > 0.95
    s_plain, s_bter = graphs.shape_stats(*plain), graphs.shape_stats(*bter)
    assert s_bter["avg_clustering"] > max(0.4, 5 * s_plain["avg_clustering"])
    assert s_bter["triangles"] > 3 * s_plain["triangles"]


def test_shape_stats_match_networkx():
    import networkx as nx

    n = SMALL["vertices"]
    off, nb = graphs.csr(graphs.structure_edges(SMALL), n)
    g = nx.Graph()
    g.add_nodes_from(range(n))
    g.add_edges_from(zip(np.repeat(np.arange(n), np.diff(off)).tolist(), nb.tolist()))
    s = graphs.shape_stats(off, nb)
    assert s["edges"] == g.number_of_edges()
    assert s["triangles"] == sum(nx.triangles(g).values()) // 3
    assert s["avg_clustering"] == pytest.approx(nx.average_clustering(g), rel=1e-9)
    assert s["closed_triangle_fraction"] == pytest.approx(nx.transitivity(g), rel=1e-9)


@pytest.mark.parametrize("config", _spec_configs(), ids=lambda c: c["name"])
def test_configuration_records_its_graph(config):
    """The statistics a configuration file records under ``derived`` are
    those of the graph it makes, at its full size."""
    n = int(config["vertices"])
    off, nb = graphs.csr(graphs.structure_edges(config), n)
    stats = graphs.shape_stats(off, nb)
    for key, value in config["derived"].items():
        if key in stats:
            assert stats[key] == pytest.approx(value, rel=1e-6), key


@pytest.mark.parametrize("query,avg_degree", [("q1", 3.0), ("q2", 4.0), ("q3", 4.0)])
def test_host_counts_match_oracle(query, avg_degree):
    from repro.core.query import PAPER_QUERIES
    from repro.graph import build_graph
    from repro.graph.oracle import count_instances

    n = 1 << 10
    edges = graphs.config_edges(dict(SMALL, avg_degree=avg_degree), 5)
    want = count_instances(build_graph(edges, n), list(PAPER_QUERIES[query].edges))
    assert want > 0
    assert reference.counts(*graphs.csr(edges, n), [query])[query] == want


def test_host_counts_on_cliques():
    from repro.core.query import PAPER_QUERIES
    from repro.graph.generators import ring_of_cliques
    from repro.graph.oracle import count_instances

    g = ring_of_cliques(6, 5)
    got = reference.counts(g.offsets, g.nbrs, ["q1", "q2", "q3"])
    for q in ("q1", "q2", "q3"):
        assert got[q] == count_instances(g, list(PAPER_QUERIES[q].edges))


@pytest.mark.parametrize("graph_seed", [1, 2, 2**31 + 7])
def test_engine_counts_on_other_draws(graph_seed):
    """A run's seed only relabels one draw, so the runs on the chip check
    one structure. Other draws of the same configuration are checked here:
    the program's 4-clique count equals the reference's on each."""
    from repro.core.engine import EngineConfig, HugeEngine
    from repro.core.query import PAPER_QUERIES
    from repro.graph import build_graph

    cfg = dict(SMALL, graph_seed=graph_seed)
    edges = graphs.config_edges(cfg, 11)
    n = cfg["vertices"]
    res = HugeEngine(build_graph(edges, n), EngineConfig(batch_size=64)).run(
        PAPER_QUERIES["q3"], space="huge")
    want = reference.counts(*graphs.csr(edges, n), ["q3"])["q3"]
    assert want > 0 and int(res.count) == want


def test_approximate_counts_are_not_exact():
    """The control's counts depart from the exact ones."""
    n = 1 << 12
    off, nb = graphs.csr(graphs.structure_edges(dict(SMALL, vertices=n)), n)
    exact = reference.counts(off, nb, ["q3"])["q3"]
    est = [reference.approximate_counts(off, nb, ["q3"], s)["q3"] for s in (1, 2, 3)]
    assert exact > 0 and all(e != exact for e in est)
