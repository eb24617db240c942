#!/usr/bin/env python3
"""Chip smoke: run the enumeration main path on a TPU and check every count.

    python chip_smoke.py          # one chip: phases (a)-(e) below
    python chip_smoke.py --four   # four chips: DistributedEngine only

One process drives the chip(s) phase by phase; it starts no child. Each
phase prints its wall time, its compile time (tracing, lowering and XLA
compilation), the compile cache's hits and misses, and the device's
``bytes_in_use`` / ``peak_bytes_in_use``, all tagged with the device kind
and count. The last line of standard output is the result::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

One chip:

(a) device: the first device must be a TPU; the compile cache is placed by
    ``repro.launch.compile_cache``.
(b) correctness at small size: q1, q2, q3 in the ``huge`` plan space and q2
    in the ``seed`` space (a PUSH-JOIN plan), each with ``fused`` off and on,
    through ``HugeEngine`` on a 2^10-vertex power-law graph (average degree
    4), against the networkx oracle (``repro.graph.oracle``).
(c) real size: q3 with ``fused`` off and on on ``powerlaw_graph(2**16, 8.0)``
    (about 1 GB of device state), against a host count from the CSR, and the
    fused extend step's lowering must contain the native kernel
    (``tpu_custom_call``).
(d) service: a ``GraphService`` on a 2^12-vertex graph takes four requests
    from three tenants: q1, q2 and q3 run to completion and must equal their
    host counts; one q2 request carries a match budget and must stop inside
    it.
(e) standing query: a standing q1 on that service receives one 64-edge
    ``apply_batch``; its delta must equal the host count difference.

Four chips (``--four``): q3 in the ``huge`` space and q2 in the ``seed``
space (PUSH-JOIN with its ``all_to_all`` hash shuffle) through
``DistributedEngine`` on the same 2^16 graph sharded by owner, against host
counts; no device may hold more than 1.25x the mean of ``bytes_in_use``
after the load.

Every phase runs under a watchdog that prints the engine's progress and
exits non-zero when the phase overruns its time budget, and every engine must
report no kernel fallback and no retry. The script refuses to run with a
``REPRO_FAULT_*`` fault plan armed, and prints no result line unless every
check passed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 7
BUDGET_S = {"b": 150, "c": 300, "d": 200, "e": 120, "four": 600}


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# Host counts from the CSR (numpy / scipy; independent of the engines)
# ---------------------------------------------------------------------------


def _oriented(offsets: np.ndarray, nbrs: np.ndarray):
    """Edges oriented from lower to higher (degree, id) rank: CSR of the
    out-neighbours and the sorted keys ``u * n + v`` of every arc u→v."""
    n = len(offsets) - 1
    deg = np.diff(offsets)
    rank = np.empty(n, np.int64)
    rank[np.lexsort((np.arange(n), deg))] = np.arange(n)
    src = np.repeat(np.arange(n, dtype=np.int64), deg)
    dst = np.asarray(nbrs, np.int64)
    keep = rank[src] < rank[dst]
    s, d = src[keep], dst[keep]
    off = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(s, minlength=n), out=off[1:])
    return off, d, s * n + d


def _has_arc(keys: np.ndarray, a: np.ndarray, b: np.ndarray, n: int) -> np.ndarray:
    k = a * n + b
    i = np.minimum(np.searchsorted(keys, k), len(keys) - 1)
    return keys[i] == k


def _expand(off: np.ndarray, nb: np.ndarray, verts: np.ndarray, chunk: int = 1 << 22):
    """Yield ``(j, w)``: for every ``verts[j]``, each out-neighbour ``w``,
    in pieces of about ``chunk`` pairs."""
    cnt = off[verts + 1] - off[verts]
    ends = np.cumsum(cnt)
    lo = 0
    while lo < len(verts):
        hi = max(int(np.searchsorted(ends, (ends[lo - 1] if lo else 0) + chunk)), lo + 1)
        c = cnt[lo:hi]
        j = np.repeat(np.arange(lo, hi), c)
        first = np.repeat(np.cumsum(c) - c, c)
        w = nb[np.repeat(off[verts[lo:hi]], c) + np.arange(int(c.sum())) - first]
        yield j, w
        lo = hi


def host_counts(offsets: np.ndarray, nbrs: np.ndarray) -> dict:
    """Instance counts of q2 (diamond) and q3 (4-clique) from the CSR.

    Each triangle is listed once along its rank-oriented arcs a→b→c; a
    4-clique is a triangle plus a common out-neighbour of all three, and a
    diamond is a pair of triangles on one edge: sum over edges of C(t_e, 2).
    """
    n = len(offsets) - 1
    off, nb, keys = _oriented(np.asarray(offsets), np.asarray(nbrs))
    src = keys // n
    tri_per_arc = np.zeros(len(keys), np.int64)
    k4 = 0
    for j, w in _expand(off, nb, src):
        a, b = src[j], nb[j]
        hit = _has_arc(keys, b, w, n)
        a, b, c = a[hit], b[hit], w[hit]
        for u, v in ((a, b), (a, c), (b, c)):
            tri_per_arc += np.bincount(np.searchsorted(keys, u * n + v),
                                       minlength=len(keys))
        for t, x in _expand(off, nb, c):
            k4 += int(np.count_nonzero(
                _has_arc(keys, a[t], x, n) & _has_arc(keys, b[t], x, n)))
    diamonds = int(np.sum(tri_per_arc * (tri_per_arc - 1) // 2))
    return {"q2": diamonds, "q3": k4}


def host_c4(offsets: np.ndarray, nbrs: np.ndarray) -> int:
    """Instances of q1 (4-cycle): sum over vertex pairs i != j of
    C(common neighbours, 2), over 4 (two diagonals, both orders)."""
    import scipy.sparse as sp

    n = len(offsets) - 1
    a = sp.csr_matrix(
        (np.ones(len(nbrs), np.int64), np.asarray(nbrs), np.asarray(offsets)),
        shape=(n, n),
    )
    w = (a @ a).tocoo()
    off_diag = w.row != w.col
    c = w.data[off_diag]
    return int(np.sum(c * (c - 1) // 2) // 4)


# ---------------------------------------------------------------------------
# Phase plumbing: timing, compile accounting, device memory, watchdog
# ---------------------------------------------------------------------------


class Watchdog:
    """Ends the process (exit 3) when a phase overruns its budget, after
    printing what the running engine last reported, so a stall fails loudly
    instead of holding the chip until the call limit."""

    def __init__(self):
        self._lock = threading.Lock()
        self._phase = None
        threading.Thread(target=self._run, daemon=True).start()

    def arm(self, phase: str, budget_s: float, progress) -> None:
        with self._lock:
            self._phase = (phase, time.monotonic() + budget_s, budget_s, progress)

    def disarm(self) -> None:
        with self._lock:
            self._phase = None

    def _run(self) -> None:
        while True:
            time.sleep(1.0)
            with self._lock:
                ph = self._phase
            if ph is None or time.monotonic() < ph[1]:
                continue
            name, _, budget, progress = ph
            try:
                where = progress()
            except Exception as e:  # the report must not keep the process alive
                where = f"progress unavailable: {e!r}"
            print(f"[watchdog] phase {name} overran {budget}s; {where}", flush=True)
            sys.stderr.flush()
            os._exit(3)


class Smoke:
    def __init__(self, devices, compile_stats):
        self.compile_stats = compile_stats
        self.devices = devices
        self.tag = f"({devices[0].device_kind} x{len(devices)})"
        self.watchdog = Watchdog()
        self.progress = lambda: "no engine yet"
        self.t_start = time.perf_counter()
        self.c_start = compile_stats()

    def say(self, msg: str) -> None:
        print(msg, flush=True)

    def memory(self, dev=None) -> dict:
        st = (dev or self.devices[0]).memory_stats() or {}
        return {k: int(st[k]) for k in ("bytes_in_use", "peak_bytes_in_use") if k in st}

    def run(self, name: str, fn) -> None:
        c0, t0 = self.compile_stats(), time.perf_counter()
        self.watchdog.arm(name, BUDGET_S[name], lambda: self.progress())
        fn()
        self.watchdog.disarm()
        c1 = self.compile_stats()
        mem = {i: self.memory(d) for i, d in enumerate(self.devices)}
        self.say(
            f"[=] phase {name}: wall={time.perf_counter() - t0:.2f}s "
            f"compile={c1['compile_s'] - c0['compile_s']:.2f}s "
            f"cache_hits={c1['cache_hits'] - c0['cache_hits']} "
            f"cache_misses={c1['cache_misses'] - c0['cache_misses']} "
            f"memory={mem if len(mem) > 1 else mem[0]} {self.tag}"
        )

    def timed(self, fn):
        """(result, wall seconds, compile seconds) of ``fn()``."""
        c0, t0 = self.compile_stats()["compile_s"], time.perf_counter()
        out = fn()
        return out, time.perf_counter() - t0, self.compile_stats()["compile_s"] - c0

    def finish(self) -> None:
        c = self.compile_stats()
        self.say(
            f"[=] all phases passed: wall={time.perf_counter() - self.t_start:.2f}s "
            f"compile={c['compile_s'] - self.c_start['compile_s']:.2f}s "
            f"cache_hits={c['cache_hits'] - self.c_start['cache_hits']} "
            f"cache_misses={c['cache_misses'] - self.c_start['cache_misses']} "
            f"{self.tag}"
        )


def _engine_clean(stats, where: str) -> None:
    """No hidden degradation: an exact count reached through a kernel
    fallback or a recovery retry would not prove the native path."""
    if isinstance(stats, dict):
        fallbacks, retries = stats["kernel_fallbacks"], stats["retries"]
    else:
        fallbacks, retries = stats.kernel_fallbacks, stats.retries
    check(fallbacks == 0, f"{where}: {fallbacks} kernel fallbacks")
    check(retries == 0, f"{where}: {retries} recovery retries")


# ---------------------------------------------------------------------------
# One-chip phases
# ---------------------------------------------------------------------------


def phase_small(sm: Smoke, vertices: int = 1 << 10) -> None:
    """(b) q1-q3 (huge) and q2 (seed) x fused off/on against networkx.

    The networkx oracle is pure Python and takes tens of seconds on this
    graph, so it runs on a host thread while the device works; its counts
    are compared once both are done."""
    from repro.core.engine import EngineConfig, HugeEngine
    from repro.core.query import PAPER_QUERIES
    from repro.graph import powerlaw_graph
    from repro.graph.oracle import count_instances

    g = powerlaw_graph(vertices, 4.0, seed=SEED)
    oracle = {}

    def count_all():
        for qname in ("q1", "q2", "q3"):
            oracle[qname] = count_instances(g, list(PAPER_QUERIES[qname].edges))

    host = threading.Thread(target=count_all, daemon=True)
    host.start()
    cases = [("q1", "huge"), ("q2", "huge"), ("q3", "huge"), ("q2", "seed")]
    runs = []
    for fused in (False, True):
        # Join buffers sized to this graph: the left side of the seed plan
        # holds a few thousand triangle rows, and a probe of 256 right rows
        # emits at most 256 x max degree (128) matches.
        eng = HugeEngine(g, EngineConfig(
            batch_size=256, fused=fused,
            join_buffer_capacity=1 << 13, join_out_capacity=1 << 15,
        ))
        for qname, space in cases:
            q = PAPER_QUERIES[qname]
            if space == "seed":
                check(eng.to_flow(q, space).num_joins() >= 1,
                      "seed-space q2 plan has no PUSH-JOIN")
            sm.progress = lambda e=eng: f"batches={e.stats.batches}"
            res, wall, comp = sm.timed(lambda: eng.run(q, space=space))
            sm.say(f"[b] 2^{vertices.bit_length() - 1} {qname}/{space} "
                   f"fused={fused} count={res.count} wall={wall:.2f}s "
                   f"compile={comp:.2f}s batches={res.stats.batches} {sm.tag}")
            runs.append((qname, space, fused, res.count))
            _engine_clean(res.stats, f"{qname}/{space} fused={fused}")
            eng.stats = type(eng.stats)()
    sm.progress = lambda: f"waiting for the networkx oracle, have {sorted(oracle)}"
    host.join()
    sm.say(f"[b] networkx oracle {oracle}")
    for qname, space, fused, count in runs:
        check(count == oracle[qname],
              f"{qname}/{space} fused={fused}: {count} != {oracle[qname]}")


def fused_step_is_native(eng, ext=(0, 1)) -> bool:
    """Whether the fused extend step, lowered at this engine's shapes,
    calls the Mosaic kernel (and not its interpret-mode or jnp twin)."""
    import jax.numpy as jnp

    from repro.core import operators as ops_mod

    b = eng.cfg.batch_size
    rows = jnp.zeros((b, len(ext)), jnp.int32)
    tab0, tab1, idx, sel, ok = eng._fused_tables(rows, ext)
    text = ops_mod.fused_extend_batch.lower(
        tab0, tab1, idx, sel, ok, rows, jnp.int32(b), lt=(), gt=(),
        out_cap=b * eng.d_pad,
    ).as_text()
    return "tpu_custom_call" in text


def phase_real(sm: Smoke, vertices: int = 1 << 16, batch: int = 1024) -> None:
    """(c) q3 on the 2^16 graph, fused off and on, against the host count."""
    import gc

    from repro.core.engine import EngineConfig, HugeEngine
    from repro.core.query import PAPER_QUERIES
    from repro.graph import powerlaw_graph

    (g, wall, _) = sm.timed(lambda: powerlaw_graph(vertices, 8.0, seed=SEED))
    sm.say(f"[c] graph 2^{vertices.bit_length() - 1}: {g.num_edges} edges, "
           f"d_pad={g.padded.d_pad}, {g.size_bytes()} bytes, built in {wall:.2f}s")
    (host, wall, _) = sm.timed(lambda: host_counts(g.offsets, g.nbrs))
    sm.say(f"[c] host counts q2={host['q2']} q3={host['q3']} in {wall:.2f}s")
    q3 = PAPER_QUERIES["q3"]
    for fused in (False, True):
        eng = HugeEngine(g, EngineConfig(batch_size=batch, fused=fused))
        sm.progress = lambda e=eng: f"batches={e.stats.batches}"
        res, wall, comp = sm.timed(lambda: eng.run(q3))
        sm.say(f"[c] q3 fused={fused} count={res.count} host={host['q3']} "
               f"wall={wall:.2f}s compile={comp:.2f}s batches={res.stats.batches} "
               f"memory={sm.memory()} {sm.tag}")
        check(res.count == host["q3"], f"2^16 q3 fused={fused}: "
              f"{res.count} != {host['q3']}")
        _engine_clean(res.stats, f"2^16 q3 fused={fused}")
        if fused:
            native = fused_step_is_native(eng)
            sm.say(f"[c] fused extend step lowers to tpu_custom_call: {native}")
            check(native, "fused extend step does not call the native kernel")
        del eng, res
        gc.collect()


def phase_service(sm: Smoke, vertices: int = 1 << 12, batch: int = 512):
    """(d) GraphService: three tenants' q1-q3 plus one budgeted q2."""
    from repro.core.engine import EngineConfig
    from repro.graph import powerlaw_graph
    from repro.serve.graph_service import (
        BUDGET_EXCEEDED, DONE, GraphQueryRequest, GraphService, ServiceConfig,
    )

    g = powerlaw_graph(vertices, 8.0, seed=SEED)
    host = dict(host_counts(g.offsets, g.nbrs), q1=host_c4(g.offsets, g.nbrs))
    budget = max(1, host["q2"] // 4)
    svc = GraphService(
        g,
        ServiceConfig(queue_capacity=1 << 14, max_active=4, tick_steps=8),
        EngineConfig(batch_size=batch),
    )
    asks = [("tenant0", "q1", None), ("tenant1", "q2", None),
            ("tenant2", "q3", None), ("tenant2", "q2", budget)]
    tickets = [svc.submit(GraphQueryRequest(tenant=t, query=q, match_budget=b))
               for t, q, b in asks]
    sm.progress = lambda: (f"ticks={svc.ticks} "
                           f"statuses={[t.status for t in tickets]}")
    summary, wall, comp = sm.timed(svc.run_until_idle)
    for tk in tickets:
        q, b = tk.request.query, tk.request.match_budget
        sm.say(f"[d] #{tk.id} {tk.request.tenant} {q} budget={b} -> {tk.status} "
               f"count={tk.count} host={host[q]} latency={tk.latency_s:.2f}s")
        if b is None:
            check(tk.status == DONE and tk.count == host[q],
                  f"service {q}: {tk.status} count {tk.count} != {host[q]}")
        else:
            check(tk.status == BUDGET_EXCEEDED and b <= tk.count < host[q],
                  f"budgeted {q}: {tk.status} count {tk.count} outside "
                  f"[{b}, {host[q]})")
        _engine_clean(tk.stats, f"service {q}")
    sm.say(f"[d] {len(tickets)} requests, {summary['ticks']} ticks, "
           f"wall={wall:.2f}s compile={comp:.2f}s {sm.tag}")
    return svc, host["q1"]


def phase_standing(sm: Smoke, svc, c4_before: int, new_edges: int = 64) -> None:
    """(e) one 64-edge batch into a standing q1; delta vs host C4 difference."""
    from repro.graph.storage import GraphUpdateBatch
    from repro.serve.graph_service import DONE

    g = svc.engine.graph
    n = g.num_vertices
    rng = np.random.default_rng(SEED)
    present = set((np.repeat(np.arange(n), np.diff(g.offsets)) * n + g.nbrs).tolist())
    edges = []
    while len(edges) < new_edges:
        u, v = (int(x) for x in rng.integers(0, n, 2))
        if u != v and u * n + v not in present:
            present.update((u * n + v, v * n + u))
            edges.append((u, v))
    sq = svc.register_standing("tenant0", "q1")
    sm.progress = lambda: f"ticks={svc.ticks}"
    out, wall, comp = sm.timed(
        lambda: svc.apply_batch(GraphUpdateBatch(np.asarray(edges))))
    g2 = svc.engine.graph
    expect = host_c4(g2.offsets, g2.nbrs) - c4_before
    (ticket,) = out["tickets"]
    delta = out["deltas"][sq.id]
    sm.say(f"[e] standing q1 +{out['new_edges']} edges: delta={delta} "
           f"host diff={expect} status={ticket.status} wall={wall:.2f}s "
           f"compile={comp:.2f}s {sm.tag}")
    check(out["new_edges"] == new_edges, f"{out['new_edges']} edges applied")
    check(ticket.status == DONE and delta == expect,
          f"standing q1 delta {delta} ({ticket.status}) != {expect}")
    _engine_clean(ticket.stats, "standing q1")


# ---------------------------------------------------------------------------
# Four-chip phase
# ---------------------------------------------------------------------------


def phase_four(sm: Smoke, vertices: int = 1 << 16, batch: int = 1024) -> None:
    """q3/huge and q2/seed through DistributedEngine on the sharded graph."""
    from repro.core.distributed import DistConfig, DistributedEngine
    from repro.core.query import PAPER_QUERIES
    from repro.graph import powerlaw_graph
    from repro.launch.mesh import auto_mesh

    g = powerlaw_graph(vertices, 8.0, seed=SEED)
    (host, wall, _) = sm.timed(lambda: host_counts(g.offsets, g.nbrs))
    sm.say(f"[4] graph 2^{vertices.bit_length() - 1}: {g.num_edges} edges, "
           f"d_pad={g.padded.d_pad}; host counts q2={host['q2']} "
           f"q3={host['q3']} in {wall:.2f}s")
    p = len(sm.devices)
    mesh = auto_mesh((p,), ("shards",))
    # The seed plan buffers the graph's 52,285 triangles on the left (at most
    # three rows each; the buffer holds them all even on one shard); a probe
    # step pops ``batch`` wedges, each matching at most max_e t_e = 400 of
    # them, so the output capacity covers the worst batch.
    eng = DistributedEngine(g, mesh, DistConfig(
        batch_size=batch, queue_capacity=1 << 16,
        join_buffer_capacity=1 << 18, join_out_capacity=1 << 19,
    ))
    used = [sm.memory(d).get("bytes_in_use", 0) for d in sm.devices]
    mean = sum(used) / len(used)
    sm.say(f"[4] loaded: bytes_in_use per device {used} (mean {mean:.0f}) {sm.tag}")
    check(max(used) <= 1.25 * mean, f"device bytes {used} exceed 1.25x the mean")
    sm.progress = lambda: f"rounds={eng.stats.get('rounds')}"
    for qname, space in (("q3", "huge"), ("q2", "seed")):
        (count, stats), wall, comp = sm.timed(
            lambda: eng.run(PAPER_QUERIES[qname], space=space))
        sm.say(f"[4] {qname}/{space} count={count} host={host[qname]} "
               f"wall={wall:.2f}s compile={comp:.2f}s rounds={stats['rounds']} "
               f"a2a_calls={stats['a2a_calls']} joins={stats['joins']} "
               f"shuffle_rows={stats['shuffle_rows']} {sm.tag}")
        check(count == host[qname], f"{qname}/{space}: {count} != {host[qname]}")
        check(stats["a2a_calls"] > 0, f"{qname}/{space}: no all_to_all ran")
        if space == "seed":
            check(stats["joins"] >= 1, "seed-space q2 ran no PUSH-JOIN")
        _engine_clean(stats, f"{qname}/{space}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four", action="store_true",
                    help="run DistributedEngine on a four-chip host, nothing else")
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repository sources at {src}", file=sys.stderr)
        return 2
    armed = sorted(k for k in os.environ if k.startswith("REPRO_FAULT_"))
    if armed:
        print(f"chip_smoke: refusing to run with a fault plan armed: {armed}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.launch.compile_cache import compile_stats, enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform} devices)",
              file=sys.stderr)
        return 1
    want = 4 if args.four else 1
    if len(devs) < want:
        print(f"chip_smoke: {len(devs)} chips, {want} needed", file=sys.stderr)
        return 1
    sm = Smoke(devs, compile_stats)
    sm.say(f"[a] device {devs[0].platform} {devs[0].device_kind} "
           f"x{len(devs)}; compile cache {cache_dir}")
    if args.four:
        sm.run("four", lambda: phase_four(sm))
    else:
        sm.run("b", lambda: phase_small(sm))
        sm.run("c", lambda: phase_real(sm))
        state = {}
        sm.run("d", lambda: state.update(svc=phase_service(sm)))
        svc, c4 = state["svc"]
        sm.run("e", lambda: phase_standing(sm, svc, c4))
    sm.finish()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
