"""Chip benchmark of the HUGE enumeration engines (see ``BENCHMARK.json``).

Run one cell from the repository root::

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything the harness needs is found by name: a configuration under
``bench/configs/``, a traffic mix under ``bench/mixes/``, the generator the
mix names under ``bench/traffic/``, and each per-layer metric's reader under
``bench/metrics/``.
"""
