"""Multi-device tests (8 host devices via subprocess — XLA device count must
be set before jax initialises, so these run in fresh interpreters)."""
import os
import subprocess
import sys
import textwrap

import pytest

ENV = dict(os.environ, PYTHONPATH="src",
           XLA_FLAGS="--xla_force_host_platform_device_count=8")


def run_py(code: str, timeout=540, devices=8) -> str:
    env = dict(ENV, XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    r = subprocess.run([sys.executable, "-u", "-c", textwrap.dedent(code)],
                       env=env, cwd="/root/repo", capture_output=True,
                       text=True, timeout=timeout)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_distributed_engine_matches_oracle():
    out = run_py("""
        import jax
        from repro.launch.mesh import auto_mesh
        from repro.graph import erdos_renyi
        from repro.graph.oracle import count_instances
        from repro.core import query as Q
        from repro.core.distributed import DistributedEngine, DistConfig
        mesh = auto_mesh((8,), ("shards",))
        g = erdos_renyi(250, 6.0, seed=11)
        eng = DistributedEngine(g, mesh, DistConfig(batch_size=128, queue_capacity=1<<14))
        for qname in ("q1", "q2", "q3"):
            q = Q.PAPER_QUERIES[qname]
            count, _ = eng.run(q)
            oracle = count_instances(g, list(q.edges))
            assert count == oracle, (qname, count, oracle)
            print(qname, "ok", count)
    """)
    assert out.count("ok") == 3


def test_distributed_work_stealing_toggle():
    out = run_py("""
        import jax
        from repro.launch.mesh import auto_mesh
        from repro.graph import powerlaw_graph
        from repro.graph.oracle import count_instances
        from repro.core import query as Q
        from repro.core.distributed import DistributedEngine, DistConfig
        mesh = auto_mesh((8,), ("shards",))
        g = powerlaw_graph(300, 6.0, seed=12)
        q = Q.PAPER_QUERIES["q1"]
        oracle = count_instances(g, list(q.edges))
        for rb in (True, False):
            eng = DistributedEngine(g, mesh, DistConfig(batch_size=128, queue_capacity=1<<14, rebalance=rb))
            count, _ = eng.run(q)
            assert count == oracle, (rb, count, oracle)
        print("stealing ok")
    """)
    assert "stealing ok" in out


def test_distributed_push_join_hybrid_plans():
    """The tentpole claim: optimiser plans containing PUSH-JOINs execute
    end-to-end on the 4-shard shard_map engine — hash-a2a shuffles, local
    probes, no single-process fallback — and match the networkx oracle on
    power-law and clique-heavy graphs."""
    out = run_py("""
        import jax
        from repro.launch.mesh import auto_mesh
        from repro.graph import powerlaw_graph, ring_of_cliques
        from repro.graph.oracle import count_instances
        from repro.core import query as Q
        from repro.core.distributed import DistributedEngine, DistConfig
        mesh = auto_mesh((4,), ("shards",))
        pl = powerlaw_graph(240, 5.0, seed=3)
        cl = ring_of_cliques(24, 5)
        cases = [
            (pl, "q1", "seed"),        # push-only space: edge scans + hash join
            (pl, "q7", "huge"),        # hybrid: optimiser mixes extends + join
            (cl, "q2", "seed"),
            (cl, "q8", "starjoin"),    # two chained joins
        ]
        engines = {}
        for g, qname, space in cases:
            if id(g) not in engines:
                engines[id(g)] = DistributedEngine(
                    g, mesh, DistConfig(batch_size=128, queue_capacity=1 << 14))
            eng = engines[id(g)]
            count, stats = eng.run(Q.PAPER_QUERIES[qname], space=space)
            assert stats["engine"] == "shard_map"     # no single-process fallback
            assert stats["joins"] >= 1, (qname, space)
            assert stats["probe_batches"] > 0, (qname, space)
            oracle = count_instances(g, list(Q.PAPER_QUERIES[qname].edges))
            assert count == oracle, (qname, space, count, oracle)
            print(qname, space, "ok", count, "shuffled", stats["shuffle_rows"])
    """, devices=4)
    assert out.count("ok") == 4


def test_distributed_probe_resumes_past_out_capacity():
    """A probe emits only as many matches as join_out_capacity holds and
    leaves the rest of its rows for the next probe: a small output capacity
    takes more probe steps, no recovery, and the same oracle count."""
    out = run_py("""
        from repro.launch.mesh import auto_mesh
        from repro.graph import powerlaw_graph
        from repro.graph.oracle import count_instances
        from repro.core import query as Q
        from repro.core.distributed import DistributedEngine, DistConfig
        mesh = auto_mesh((4,), ("shards",))
        g = powerlaw_graph(300, 6.0, seed=5)
        q = Q.PAPER_QUERIES["q2"]
        oracle = count_instances(g, list(q.edges))
        probes = []
        for out_cap in (1 << 14, 64):
            eng = DistributedEngine(g, mesh, DistConfig(
                batch_size=128, queue_capacity=1 << 14, join_out_capacity=out_cap))
            count, stats = eng.run(q, space="seed")
            assert count == oracle, (out_cap, count, oracle)
            assert stats["retries"] == 0 and stats["joins"] == 1, stats
            probes.append(stats["probe_batches"])
        assert probes[1] > probes[0], probes
        print("ok", oracle, probes)
    """, devices=4)
    assert "ok" in out


def test_distributed_fused_hot_path_matches_unfused():
    """The fused extend/verify and probe kernels inside the shard_map engine
    produce counts identical to the unfused collectives path and the oracle —
    ref twins at scale, plus a small interpret-mode (force_kernel) run that
    executes real Pallas kernel semantics inside shard_map."""
    out = run_py("""
        import jax
        from repro.launch.mesh import auto_mesh
        from repro.graph import powerlaw_graph, ring_of_cliques
        from repro.graph.oracle import count_instances
        from repro.core import query as Q
        from repro.core.distributed import DistributedEngine, DistConfig
        mesh = auto_mesh((4,), ("shards",))
        pl = powerlaw_graph(240, 5.0, seed=3)
        for qname, space in (("q1", "huge"), ("q2", "seed"), ("q7", "huge")):
            q = Q.PAPER_QUERIES[qname]
            oracle = count_instances(pl, list(q.edges))
            base, _ = DistributedEngine(pl, mesh, DistConfig(
                batch_size=128, queue_capacity=1 << 14)).run(q, space=space)
            fused, _ = DistributedEngine(pl, mesh, DistConfig(
                batch_size=128, queue_capacity=1 << 14, fused=True)).run(q, space=space)
            assert base == fused == oracle, (qname, space, base, fused, oracle)
            print(qname, space, "ok", fused)
        # interpret-mode kernels inside shard_map on a tiny clique graph
        cl = ring_of_cliques(4, 5)
        q = Q.PAPER_QUERIES["q2"]
        oracle = count_instances(cl, list(q.edges))
        fused, _ = DistributedEngine(cl, mesh, DistConfig(
            batch_size=16, queue_capacity=1 << 10, join_buffer_capacity=1 << 9,
            join_out_capacity=1 << 10, fused=True, force_kernel=True)).run(q)
        assert fused == oracle, (fused, oracle)
        print("interpret ok", fused)
    """, devices=4)
    assert out.count("ok") == 4


def test_distributed_mixed_tenants_run_concurrent():
    """Three tenants' queries execute through ONE shard_map engine in a single
    merged scheduler pass (tenant-tagged runtimes); per-tenant counts must
    equal both isolated runs and the networkx oracle."""
    out = run_py("""
        import jax
        from repro.launch.mesh import auto_mesh
        from repro.graph import erdos_renyi
        from repro.graph.oracle import count_instances
        from repro.core import query as Q
        from repro.core.distributed import DistributedEngine, DistConfig
        mesh = auto_mesh((4,), ("shards",))
        g = erdos_renyi(200, 5.0, seed=13)
        eng = DistributedEngine(g, mesh, DistConfig(batch_size=128, queue_capacity=1<<14))
        queries = [Q.PAPER_QUERIES[n] for n in ("q1", "q2", "q3")]
        counts, stats = eng.run_concurrent(queries)
        assert stats["tenants"] == 3 and stats["per_tenant_matches"] == counts
        labels = [rt.label for rt in eng._last_runtimes]
        assert any(l.startswith("t0:") for l in labels)
        assert any(l.startswith("t2:") for l in labels)
        for q, got in zip(queries, counts):
            alone, _ = eng.run(q)
            oracle = count_instances(g, list(q.edges))
            assert got == alone == oracle, (q.name, got, alone, oracle)
            print(q.name, "ok", got)
    """, devices=4)
    assert out.count("ok") == 3


def test_moe_push_pull_equivalence_multidevice():
    """HUGE's core claim for the LM substrate: push and pull modes are the
    same logical join — identical outputs, different collectives."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import auto_mesh
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.models import sharding as shd
        from repro.models.moe import moe_init, moe_block
        mesh = auto_mesh((4, 2), ("data", "model"))
        key = jax.random.key(0)
        params = moe_init(key, 32, 64, 8, jnp.float32)
        x = jax.random.normal(jax.random.key(1), (8, 16, 32), jnp.float32)
        with shd.activate(mesh), mesh:
            outs = {}
            for mode in ("local", "push", "pull"):
                f = jax.jit(lambda p, x: moe_block(p, x, experts_per_token=2, comm_mode=mode))
                outs[mode] = np.asarray(f(params, x))
            e1 = np.max(np.abs(outs["push"] - outs["local"]))
            e2 = np.max(np.abs(outs["pull"] - outs["local"]))
            assert e1 < 1e-4 and e2 < 1e-4, (e1, e2)
            # the collective schedules must actually differ
            hp = jax.jit(lambda p, x: moe_block(p, x, experts_per_token=2, comm_mode="push")).lower(params, x).compile().as_text()
            hl = jax.jit(lambda p, x: moe_block(p, x, experts_per_token=2, comm_mode="pull")).lower(params, x).compile().as_text()
            assert "all-to-all" in hp
            assert "all-gather" in hl
        print("moe ok", float(e1), float(e2))
    """)
    assert "moe ok" in out


def test_compressed_psum_accuracy():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import auto_mesh
        from repro.train.compress import compressed_psum_mean
        mesh = auto_mesh((8,), ("pod",))
        x = jax.random.normal(jax.random.key(0), (10000,), jnp.float32)
        with mesh:
            got = compressed_psum_mean(x, "pod", mesh)
        # all shards hold the same x → mean == x, up to int8 quantisation
        rel = float(jnp.max(jnp.abs(got - x)) / jnp.max(jnp.abs(x)))
        assert rel < 0.02, rel
        print("compress ok", rel)
    """)
    assert "compress ok" in out


def test_train_step_runs_sharded():
    """A real sharded train step on a (4, 2) mesh: loss finite, params move."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import auto_mesh
        from repro.configs import smoke_config
        from repro.models import sharding as shd
        from repro.models.partitioning import param_shardings
        from repro.train.train_step import TrainConfig, make_train_step, init_all
        from repro.train.optimizer import AdamWConfig
        cfg = smoke_config("qwen3-moe-30b-a3b")
        mesh = auto_mesh((4, 2), ("data", "model"))
        tc = TrainConfig(adamw=AdamWConfig(learning_rate=1e-3))
        with shd.activate(mesh), mesh:
            params, opt = init_all(cfg, tc, jax.random.key(0))
            params = jax.device_put(params, param_shardings(cfg, params, mesh))
            step = jax.jit(make_train_step(cfg, tc), donate_argnums=(0, 1))
            toks = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (8, 32)))
            l0 = None
            for i in range(6):
                params, opt, m = step(params, opt, {"tokens": toks})
                l0 = l0 or float(m["loss"])
            assert float(m["loss"]) < l0
        print("sharded train ok", l0, float(m["loss"]))
    """)
    assert "sharded train ok" in out


def test_elastic_reshard_8_to_4(tmp_path):
    d = str(tmp_path / "ck")
    run_py(f"""
        import jax
        from repro.configs import smoke_config
        from repro.train.train_step import TrainConfig, init_all
        from repro.train import checkpoint as ckpt
        cfg = smoke_config("granite-3-8b")
        tc = TrainConfig()
        params, opt = init_all(cfg, tc, jax.random.key(0))
        ckpt.save({d!r}, 3, params, opt)
        print("saved on", len(jax.devices()))
    """)
    # reload on a DIFFERENT device count (4) and keep training
    env4 = dict(ENV, XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-u", "-c", textwrap.dedent(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import smoke_config
        from repro.models import sharding as shd
        from repro.train.elastic import make_mesh_from_available, reshard_checkpoint
        from repro.train.train_step import TrainConfig, make_train_step
        cfg = smoke_config("granite-3-8b")
        tc = TrainConfig()
        mesh = make_mesh_from_available(model_axis=2)
        with shd.activate(mesh), mesh:
            params, opt, _ = reshard_checkpoint({d!r}, 3, cfg, tc, mesh)
            step = jax.jit(make_train_step(cfg, tc))
            toks = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (4, 16)))
            params, opt, m = step(params, opt, {{"tokens": toks}})
            assert bool(jnp.isfinite(m["loss"]))
        print("elastic ok", len(jax.devices()))
    """)], env=env4, cwd="/root/repo", capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "elastic ok 4" in r.stdout


def test_hlo_counter_counts_collectives_in_loops():
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import auto_mesh
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.hlo_counter import analyze
        mesh = auto_mesh((8,), ("data",))
        def f(x, w):
            def body(c, _):
                y = jax.lax.with_sharding_constraint(c @ w, NamedSharding(mesh, P(None, None)))
                return y, None
            y, _ = jax.lax.scan(body, x, None, length=7)
            return jnp.sum(y)
        xs = jax.ShapeDtypeStruct((128, 256), jnp.float32)
        ws = jax.ShapeDtypeStruct((256, 256), jnp.float32)
        with mesh:
            c = jax.jit(f, in_shardings=(NamedSharding(mesh, P(None, "data")), NamedSharding(mesh, P("data", None)))).lower(xs, ws).compile()
        cnt = analyze(c.as_text())
        # counts are PER DEVICE: the matmul is contraction-sharded 8 ways
        expect = 7 * 2 * 128 * 256 * 256 / 8
        assert abs(cnt.flops - expect) / expect < 0.01, cnt.flops
        assert cnt.coll_calls.get("all-reduce", 0) >= 7
        print("counter ok", cnt.flops, cnt.coll)
    """)
    assert "counter ok" in out
