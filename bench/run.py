"""Run one benchmark cell on the chip and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the repository root. The cell is looked up in ``BENCHMARK.json``; its
configuration file, its traffic mix (``bench/mixes/<traffic>.json``), the
generator that mix names (``bench/traffic/<kind>.py``) and each per-layer
metric's reader (``bench/metrics/<metric>.py``) are found by name, so this
file names no cell, configuration or metric.

A run: set-up (JAX start, graph build, device load, warm-up of the cell's own
shapes; ``setup_s`` is process start to the first timed request), then the
timed window of ``--seconds``, then, with the program's state freed, the
comparison with the plain reference that decides ``correct``. With
``--trace 1`` the window runs under the profiler, and the per-layer metrics
are read from the trace and the run's counters. The last line of standard
output is one JSON object; the last lines of standard error are the numbers
compared, each with its limit.

The run fails (non-zero exit, no result line) when the first device is not a
TPU, when there are fewer chips than the cell asks for, or when the
program's sources are not beside the benchmark.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Dict, List, Optional, Tuple  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


class BenchmarkError(RuntimeError):
    """The benchmark cannot run this cell here (exit code 2)."""


# ---------------------------------------------------------------------------
# Finding a cell's parts by name
# ---------------------------------------------------------------------------


def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _named(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchmarkError(f"no {what} named {name!r} in BENCHMARK.json")


def load_module(path: Path, name: str):
    """Import a file of the benchmark by path (metric files carry dots)."""
    if not path.is_file():
        raise BenchmarkError(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(spec: dict, workload: str, root: Path = ROOT):
    """``(cell, config, mix, generator)`` of a cell, each from its own file."""
    cell = _named(spec["workloads"], workload, "workload")
    entry = _named(spec["configs"], cell["config"], "configuration")
    with open(root / entry["file"]) as f:
        config = json.load(f)
    mix_path = root / "bench" / "mixes" / f"{cell['traffic']}.json"
    if not mix_path.is_file():
        raise BenchmarkError(f"missing traffic mix {mix_path.relative_to(root)}")
    with open(mix_path) as f:
        mix = json.load(f)
    gen = load_module(root / "bench" / "traffic" / f"{mix['kind']}.py",
                      f"bench_traffic_{mix['kind']}")
    return cell, config, mix, gen


def cell_metrics(spec: dict, cell: str, section: str) -> List[dict]:
    """The metrics of ``section`` that this cell reports: those that list it
    under ``workloads``, or, without that key, every end-to-end metric and
    every per-layer metric whose end-to-end metric the cell reports."""
    own_e2e = {m["name"] for m in spec["end_to_end"]
               if cell in m.get("workloads", [cell])}
    out = []
    for m in spec[section]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m.get("moves") in own_e2e:
            out.append(m)
    return out


def metric_reader(name: str, root: Path = ROOT):
    return load_module(root / "bench" / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_"))


# ---------------------------------------------------------------------------
# What a generator is handed: the cell's data, spans, set-up phases, a log
# ---------------------------------------------------------------------------


class Context:
    """One run's settings and bookkeeping, handed to the traffic generator.

    ``span(name)`` opens a ``jax.profiler.TraceAnnotation`` while the run is
    traced (and nothing otherwise); ``phase(name)`` records one part of the
    set-up with the compile seconds, cache hits and misses inside it."""

    def __init__(self, cell: dict, config: dict, mix: dict, seed: int,
                 seconds: float, devices: list, tracing: bool,
                 compile_stats: Callable[[], dict], log: Callable[[str], None]):
        self.cell, self.config, self.mix = cell, config, mix
        self.seed, self.seconds = int(seed), float(seconds)
        self.devices, self.tracing = devices, tracing
        self.compile_stats = compile_stats
        self.log = log
        self.span_names: set = set()
        self.phases: List[Tuple[str, float, dict]] = []
        self._unwrap: List[Callable[[], None]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        self.span_names.add(name)
        if not self.tracing:
            yield
            return
        import jax

        with jax.profiler.TraceAnnotation(name):
            yield

    @contextlib.contextmanager
    def phase(self, name: str):
        c0, t0 = self.compile_stats(), time.perf_counter()
        yield
        c1 = self.compile_stats()
        self.phases.append((name, time.perf_counter() - t0,
                            {k: c1[k] - c0[k] for k in c1}))

    def wrap_spans(self, owner, method: str, name_of: Callable) -> None:
        """While traced, open a span named ``name_of(self_obj)`` around every
        call of ``owner.method`` (a class of the program): spans from the
        benchmark's files around the calls into a layer."""
        if not self.tracing or not hasattr(owner, method):
            return
        import jax

        inner = getattr(owner, method)
        names = self.span_names

        def wrapped(obj, *a, **k):
            label = name_of(obj)
            names.add(label)
            with jax.profiler.TraceAnnotation(label):
                return inner(obj, *a, **k)

        setattr(owner, method, wrapped)
        self._unwrap.append(lambda: setattr(owner, method, inner))

    def unwrap_spans(self) -> None:
        while self._unwrap:
            self._unwrap.pop()()


# ---------------------------------------------------------------------------
# One run of one cell
# ---------------------------------------------------------------------------


def run_cell(spec: dict, workload: str, seed: int, seconds: float,
             trace: bool, devices: list, *, t_process: float,
             compile_stats: Callable[[], dict],
             log: Callable[[str], None] = None,
             root: Path = ROOT,
             config_override: Optional[dict] = None) -> dict:
    """Set up, measure and check one run; return the result object.

    ``config_override`` replaces entries of the cell's configuration (the
    tests run cells at a size a CPU holds)."""
    cell, config, mix, gen = resolve(spec, workload, root)
    config = dict(config, **(config_override or {}))
    tag = f"[{devices[0].platform} {devices[0].device_kind} x{len(devices)}]"
    log = log or (lambda m: print(f"{tag} {m}", file=sys.stderr, flush=True))
    ctx = Context(cell, config, mix, seed, seconds, devices, trace,
                  compile_stats, log)
    ctx.phases.append(("jax_start", time.perf_counter() - t_process,
                       dict(compile_stats())))
    try:
        return _measure(spec, workload, trace, devices, t_process,
                        compile_stats, log, root, ctx, gen)
    finally:
        ctx.unwrap_spans()


def _measure(spec, workload, trace, devices, t_process, compile_stats, log,
             root, ctx, gen) -> dict:
    state = gen.setup(ctx)
    setup_s = time.perf_counter() - t_process
    for name, secs, c in ctx.phases:
        log(f"setup {name}: {secs:.3f}s compile={c['compile_s']:.3f}s "
            f"cache_hits={c['cache_hits']} cache_misses={c['cache_misses']}")
    log(f"setup_s={setup_s:.3f}")

    if trace:
        from bench import trace as trace_mod

        # A traced run reads shares of the chip; a chip with no published
        # peaks is an error before anything is measured.
        chip = trace_mod.peaks(devices[0].device_kind)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    c0 = compile_stats()
    try:
        if trace:
            import jax

            jax.profiler.start_trace(trace_dir)
        try:
            with ctx.span("bench.window"):
                out = gen.window(ctx, state)
        finally:
            if trace:
                jax.profiler.stop_trace()
        c1 = compile_stats()
        log(f"window: {out['window_s']:.3f}s, compiles inside it: "
            f"{c1['compile_s'] - c0['compile_s']:.3f}s "
            f"misses={c1['cache_misses'] - c0['cache_misses']} "
            f"hits={c1['cache_hits'] - c0['cache_hits']}")
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices)
        summary = None
        if trace:
            summary = trace_mod.load(trace_dir, sorted(ctx.span_names))
            log(f"memory peak {peak} bytes, {100 * peak / chip['hbm_bytes']:.2f}% "
                f"of the chip's {chip['hbm_bytes']:.0f} ({chip['source']})")
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    state = None  # the program's state is freed before the reference runs
    gc.collect()

    with ctx.span("bench.check"):
        checks = gen.check(ctx, out)
    correct = all(value <= limit for _, value, limit in checks)

    metrics: Dict[str, dict] = {}
    if not trace:
        # The mix names the generator's measure behind each end-to-end
        # metric; the set-up time is the harness's own.
        values = {name: out["measures"][measure]
                  for name, measure in ctx.mix["end_to_end"].items()
                  if measure in out["measures"]}
        values["setup_s"] = setup_s
        for m in cell_metrics(spec, workload, "end_to_end"):
            if m["name"] not in values:
                raise BenchmarkError(f"{workload} did not measure {m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        record = {"counters": out["counters"], "trace": summary,
                  "window_s": out["window_s"]}
        for m in cell_metrics(spec, workload, "per_layer"):
            value = metric_reader(m["name"], root).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = summary.busy_s()
        device["window_s"] = summary.window_s
        gaps = summary.idle_gaps()
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.top_programs(10)],
            "idle_gaps": [[n, s] for n, s in list(gaps.items())[:10]],
        }
        log(f"trace: busy_s={summary.busy_s():.6f} window_s={summary.window_s:.6f} "
            f"devices={len(summary.devices)} idle by span={gaps}")
        log("trace: top operations "
            f"{[(n.split(' = ')[0], s) for n, s in summary.top_programs(20, ops=True)]}")
    result["check"] = {name: {"value": value, "limit": limit}
                       for name, value, limit in checks}
    for name, value, limit in checks:
        log(f"check {name}={value} limit={limit}")
    return result


def start_jax():
    """Import JAX and the program with the persistent compile cache in the
    checkout's fixed directory, whatever the environment says (the program
    takes the directory given here). Returns ``(jax, compile_stats)``."""
    CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax

    from repro.launch.compile_cache import compile_stats, enable_compile_cache

    enable_compile_cache()
    return jax, compile_stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        spec = load_spec()
        cell, _, _, _ = resolve(spec, args.workload)
    except (BenchmarkError, OSError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program sources at {ROOT / 'src'}", file=sys.stderr)
        return 2
    jax, compile_stats = start_jax()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX found {devices[0].platform} devices)",
              file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"bench: {len(devices)} chips, the cell needs {cell['chips']}",
              file=sys.stderr)
        return 1
    result = run_cell(spec, args.workload, args.seed, args.seconds,
                      bool(args.trace), devices[:cell["chips"]],
                      t_process=T_PROCESS,
                      compile_stats=compile_stats)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
