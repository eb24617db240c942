"""The control of the comparison that decides ``correct``.

    python3 -m bench.control --workload <cell> --seeds 1,2,3

For each seed, the cell's generator answers the cell's traffic with the
plain reference put in the program's place and one stated guarantee broken
(``control`` in ``bench/traffic/<kind>.py``): approximate counts where exact
ones are stated, a LIMIT answer reported as the whole where a complete one
is stated. Those answers go through the same check as a run's, at the
cell's own size, and each number compared is printed beside its limit. The
control has to come out as not correct; the benchmark's own runs never run
it.
"""
from __future__ import annotations

import argparse
import json
import sys

from bench.run import BenchmarkError, load_spec, resolve


class _Ctx:
    def __init__(self, config, mix, seed, log):
        self.config, self.mix, self.seed, self.log = config, mix, seed, log


def control_checks(spec: dict, workload: str, seed: int,
                   config_override=None, log=None) -> list:
    """``[(name, value, limit)]`` of the control's answers on one seed."""
    _, config, mix, gen = resolve(spec, workload)
    ctx = _Ctx(dict(config, **(config_override or {})), mix, int(seed),
               log or (lambda m: print(m, file=sys.stderr)))
    out = gen.control(ctx)
    return gen.check(ctx, out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    spec = load_spec()
    try:
        resolve(spec, args.workload)
    except BenchmarkError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        checks = control_checks(spec, args.workload, seed)
        correct = all(v <= lim for _, v, lim in checks)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": correct,
                          "check": {n: {"value": v, "limit": lim}
                                    for n, v, lim in checks}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
