"""Distributed Table-1: communication modes with *real* collectives.

table1_comm_modes.py reproduces the paper's communication-mode comparison
with simulated byte accounting on the single-process engine; this suite runs
the same comparison on the shard_map SPMD engine (distributed.py), where the
bytes are what ``all_to_all`` collectives actually moved:

  pull-only  BENU space: pure extend/verify chains, GetNbrs fetch traffic;
  push-only  SEED space: hash/push plans — every join is a distributed
             PUSH-JOIN hash shuffle;
  hybrid     HUGE space: the optimiser mixes PULL-EXTEND and PUSH-JOIN
             per Eq. 3 (the paper's headline claim).

Per row we report count, pull bytes (fetch-stage remote vids × (D_pad+2)·4),
push bytes (join-shuffle rows crossing shards × row width), steal bytes, and
the Eq.-3 prediction from hybrid_comm.enum_join_mode for context.

On the CPU, XLA fixes the host device count at import, so the measurement
runs in a fresh interpreter with ``--xla_force_host_platform_device_count=8``
(same mechanism as tests/test_distributed.py). On an accelerator the process
that asks for the backend holds the chips, so the measurement runs in that
same process over every device it sees. Invoke via
``PYTHONPATH=src python -m benchmarks.run exp_dist_hybrid`` (EXPERIMENTS.md
§Distributed-hybrid).
"""
from __future__ import annotations

import os
import subprocess
import sys

SHARDS = 8
QUERIES = ("q1", "q2")  # q7+ explode at CI scale; run them via launch/enumerate
SYSTEMS = (("pull-only", "benu"), ("push-only", "seed"), ("hybrid", "huge"))


def inner(shards: int = SHARDS) -> None:
    import time

    import jax

    from benchmarks.common import emit, record_bench
    from repro.core import query as Q
    from repro.core.cost import GraphStats
    from repro.core.distributed import DistConfig, DistributedEngine
    from repro.core.hybrid_comm import enum_join_mode
    from repro.graph import powerlaw_graph

    from repro.launch.mesh import auto_mesh

    mesh = auto_mesh((shards,), ("shards",))
    graph = powerlaw_graph(1 << 9, 6.0, seed=7)
    stats = GraphStats.from_graph(graph)
    engines = {
        False: DistributedEngine(
            graph, mesh, DistConfig(batch_size=256, queue_capacity=1 << 15)
        ),
        True: DistributedEngine(
            graph, mesh,
            DistConfig(batch_size=256, queue_capacity=1 << 15, fused=True),
        ),
    }
    entries = []
    for qname in QUERIES:
        q = Q.PAPER_QUERIES[qname]
        counts = {}
        for system, space in SYSTEMS:
            for fused in (False, True):
                t0 = time.perf_counter()
                count, s = engines[fused].run(q, space=space)
                wall = time.perf_counter() - t0
                counts[(system, fused)] = count
                assert s["engine"] == "shard_map"
                mode = "fused" if fused else "unfused"
                emit(
                    f"exp_dist_hybrid/{system}/{qname}"
                    + ("/fused" if fused else ""),
                    wall * 1e6,
                    f"count={count};joins={s['joins']};a2a={s['a2a_calls']};"
                    f"pull={s['pulled_bytes'] / 1e6:.3f}MB;"
                    f"push={s['shuffle_bytes'] / 1e6:.3f}MB;"
                    f"steal={s['steal_bytes'] / 1e6:.3f}MB",
                )
                entries.append({
                    "suite": "exp_dist_hybrid", "case": f"{system}/{qname}",
                    "mode": mode, "matches": int(count),
                    "wall_s": round(wall, 4),
                    "matches_per_s": round(count / max(wall, 1e-9), 1),
                })
        assert len(set(counts.values())) == 1, f"{qname}: {counts}"
        # Eq.-3 prediction for this query's top-level join volume: use the
        # total match count as the intermediate-result proxy (CI scale).
        hybrid_count = counts[("hybrid", False)]
        dec = enum_join_mode(
            left_rows=max(hybrid_count, 1), right_rows=max(hybrid_count, 1),
            width_left=q.num_vertices, width_right=q.num_vertices,
            graph_edges=stats.num_directed_edges / 2, machines=shards,
        )
        emit(
            f"exp_dist_hybrid/eq3/{qname}", 0.0,
            f"mode={dec.mode};push={dec.push_bytes / 1e6:.3f}MB;"
            f"pull={dec.pull_bytes / 1e6:.3f}MB",
        )
    path = record_bench("fused_hotpath", entries)
    print(f"# wrote {path}")


def main() -> None:
    """Run in process on an accelerator; on the CPU, relay the measurement
    from a fresh interpreter with 8 host devices."""
    import jax

    if jax.default_backend() != "cpu":
        inner(len(jax.devices()))
        return
    env = dict(
        os.environ,
        PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH", ""),
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    r = subprocess.run(
        [sys.executable, "-c",
         "from benchmarks.exp_dist_hybrid import inner; inner()"],
        env=env, capture_output=True, text=True, timeout=1200,
    )
    sys.stdout.write(r.stdout)
    if r.returncode != 0:
        raise RuntimeError(f"exp_dist_hybrid subprocess failed:\n{r.stderr[-3000:]}")


if __name__ == "__main__":
    main()
