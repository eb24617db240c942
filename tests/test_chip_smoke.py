"""chip_smoke.py on the CPU: it must refuse to report without a chip, and
its host-side counts (the ones the chip's answers are checked against) must
agree with the networkx oracle."""
from __future__ import annotations

import importlib.util
import os
import shutil
import subprocess
import sys

import pytest

from repro.core.query import PAPER_QUERIES
from repro.graph import powerlaw_graph
from repro.graph.generators import ring_of_cliques
from repro.graph.oracle import count_instances

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(cwd, script, **env):
    full = {k: v for k, v in os.environ.items() if not k.startswith("REPRO_FAULT_")}
    full.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, script], cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("where", ["cpu", "alone", "fault-armed"])
def test_chip_smoke_fails_without_a_chip(where, tmp_path):
    """No TPU, no repository beside it, or a fault plan armed: the script
    exits non-zero and prints no result line."""
    if where == "alone":
        shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
        r = _run(tmp_path, "chip_smoke.py")
    elif where == "fault-armed":
        r = _run(ROOT, SMOKE, REPRO_FAULT_KIND="queue-overflow")
    else:
        r = _run(ROOT, SMOKE)
    assert r.returncode != 0, r.stdout
    assert '"ok": true' not in r.stdout


@pytest.mark.parametrize("graph", ["powerlaw", "cliques"])
def test_host_counts_match_oracle(graph):
    smoke = _load_smoke()
    g = (powerlaw_graph(300, 6.0, seed=1) if graph == "powerlaw"
         else ring_of_cliques(6, 5))
    got = dict(smoke.host_counts(g.offsets, g.nbrs),
               q1=smoke.host_c4(g.offsets, g.nbrs))
    want = {q: count_instances(g, list(PAPER_QUERIES[q].edges))
            for q in ("q1", "q2", "q3")}
    assert got == want


_CACHE_PROBE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import compile_stats, enable_compile_cache
path = enable_compile_cache()
jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
print(path, jax.config.jax_compilation_cache_dir, compile_stats()["cache_hits"])
"""


@pytest.mark.parametrize("where", ["env", "default"])
def test_compile_cache_placement(where, tmp_path):
    """$JAX_COMPILATION_CACHE_DIR wins when set, and a second process finds
    the first one's entries there; unset, the cache is <repo>/.jax_cache."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src") + os.pathsep + env.get("PYTHONPATH", ""))
    if where == "env":
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    runs = []
    for _ in range(2 if where == "env" else 1):
        r = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        runs.append(r.stdout.split())
    want = str(tmp_path) if where == "env" else os.path.join(ROOT, ".jax_cache")
    assert runs[0][:2] == [want, want]
    if where == "env":
        assert os.listdir(tmp_path), "no cache entry was written"
        assert int(runs[1][2]) > 0, "the second process found no cache hit"
