"""Every name in BENCHMARK.json resolves to a file of its own, the harness
names no cell, configuration or metric, and the command refuses to report
without a TPU or without the program beside it."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run

ROOT = Path(run.__file__).resolve().parents[1]
SPEC = run.load_spec(ROOT)
HARNESS = [ROOT / "bench" / f for f in
           ("run.py", "trace.py", "graphs.py", "reference.py", "control.py")
           ] + sorted((ROOT / "bench" / "traffic").glob("*.py"))


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_resolves_to_its_files(cell):
    c, config, mix, gen = run.resolve(SPEC, cell, ROOT)
    assert config["name"] == c["config"]
    for fn in ("setup", "window", "check", "control"):
        assert callable(getattr(gen, fn))
    reported = {m["name"] for m in run.cell_metrics(SPEC, cell, "end_to_end")}
    assert "setup_s" in reported and len(reported) >= 2
    assert reported - {"setup_s"} <= set(mix["end_to_end"])
    assert run.cell_metrics(SPEC, cell, "per_layer")


def test_each_configuration_has_its_own_file():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used
        assert c["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_metric_resolves_to_its_reader(metric):
    assert (ROOT / "bench" / "metrics" / f"{metric}.py").is_file()


@pytest.mark.parametrize("metric", sorted(
    p.name[:-3] for p in (ROOT / "bench" / "metrics").glob("*.py")))
def test_metric_reader_reads_nothing_from_nothing(metric):
    reader = run.metric_reader(metric, ROOT)
    assert reader.read({"counters": {}, "trace": None, "window_s": 1.0}) is None


def test_harness_names_no_cell_configuration_or_metric():
    names = ({w["name"] for w in SPEC["workloads"]}
             | {c["name"] for c in SPEC["configs"]}
             | {m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]}
             ) - {"setup_s"}  # the contract's set-up time, in every cell
    for path in HARNESS:
        text = path.read_text()
        for name in names:
            hit = re.search(rf"(?<![\w.]){re.escape(name)}(?![\w.])", text)
            assert hit is None, f"{path.name} names {name!r}"


def _bench(cwd, *args):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    r = _bench(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_refuses_alone_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _bench(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
