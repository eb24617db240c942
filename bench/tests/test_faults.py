"""A run with the timed path broken underneath comes out not correct.

Each test drives the whole of ``bench.run.run_cell`` but for the look for a
chip, on the CPU at a size a test holds, with one fault planted in the
program after set-up: a step that returns its state unchanged, half of each
batch left out, an answer altered where it is produced. (No cell runs
across chips, so no exchange between chips can be left out.) A sound run of
the same size comes out correct. The control (``bench/control.py``) comes out
not correct on every cell.
"""
from __future__ import annotations

import time
from pathlib import Path

import pytest

from bench import run
from bench.control import control_checks

ROOT = Path(run.__file__).resolve().parents[1]
SPEC = run.load_spec(ROOT)
SMALL = {"vertices": 1 << 10, "avg_degree": 6.0, "batch_size": 64}
CELLS = [w["name"] for w in SPEC["workloads"]]


def _run(cell, fault=None, seconds=1.5):
    """Run ``cell`` on the CPU; ``fault()`` is planted once set-up is done."""
    import jax

    from repro.launch.compile_cache import compile_stats

    def log(msg):
        if msg.startswith("setup_s=") and fault is not None:
            fault()

    return run.run_cell(SPEC, cell, 2**31 + 3, seconds, False,
                        jax.devices()[:1], t_process=time.perf_counter(),
                        compile_stats=compile_stats, log=log, root=ROOT,
                        config_override=SMALL)


def _extend_fault(monkeypatch, how):
    from repro.core import operators as ops_mod

    real = ops_mod.extend_batch

    def broken(*a, **k):
        out, m = real(*a, **k)
        return out, (m * 0 if how == "unchanged" else m // 2)

    return lambda: monkeypatch.setattr(ops_mod, "extend_batch", broken)


def _sink_fault(monkeypatch):
    from repro.core import engine as eng_mod

    real = eng_mod._SinkRT.run_one

    def broken(self):
        real(self)
        self.e.stats.count += 1

    return lambda: monkeypatch.setattr(eng_mod._SinkRT, "run_one", broken)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "check"


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "answer_altered"])
def test_adhoc_fault_is_caught(cell, fault, monkeypatch):
    plant = {
        "state_unchanged": lambda: _extend_fault(monkeypatch, "unchanged"),
        "half_batch": lambda: _extend_fault(monkeypatch, "half"),
        "answer_altered": lambda: _sink_fault(monkeypatch),
    }[fault]()
    res = _run(cell, plant)
    assert not res["correct"], res["check"]
    assert res["check"]["count_gap"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    for seed in (1, 2, 2**31 + 5):
        checks = control_checks(SPEC, cell, seed, config_override=SMALL,
                                log=lambda m: None)
        assert not all(v <= lim for _, v, lim in checks), checks
