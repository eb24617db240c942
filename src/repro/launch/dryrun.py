import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import (jax locks the device
# count at first init). DRYRUN_DEVICES overrides for CI-scale self-tests.
if os.environ.get("DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={os.environ['DRYRUN_DEVICES']}"
    )

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this proves, without hardware: the sharding is coherent (no
GSPMD errors), the collective schedule exists, memory_analysis fits, and
cost_analysis yields the roofline terms (§Roofline reads the JSON written
here).

Usage:
  python -m repro.launch.dryrun                         # all cells, both meshes
  python -m repro.launch.dryrun --arch granite-3-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --skip-existing         # resume a sweep
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs import ARCH_NAMES, SHAPES, get_config, shape_skip_reason
from repro.launch import hlo_counter
from repro.launch.mesh import (
    DCI_BW, HBM_BW, ICI_BW, PEAK_FLOPS_BF16, auto_mesh, make_production_mesh,
)
from repro.launch.specs import input_specs
from repro.models import sharding as shd

REPORT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "reports", "dryrun")


def _make_mesh(multi: bool):
    """Production mesh; scaled-down fallback when DRYRUN_DEVICES < 512 (CI
    self-tests only — the deliverable sweep runs at 512)."""
    n = len(jax.devices())
    need = 512 if multi else 256
    if n >= need:
        return make_production_mesh(multi_pod=multi)
    if multi:
        model = max(2, n // 4)
        return auto_mesh((2, n // (2 * model), model), ("pod", "data", "model"))
    model = max(2, n // 2)
    return auto_mesh((n // model, model), ("data", "model"))


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    multi = mesh_kind == "multi"
    mesh = _make_mesh(multi)
    chips = int(len(jax.devices()))
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "mesh_shape": dict(mesh.shape), "chips": chips, "ok": False,
    }
    skip = shape_skip_reason(arch, shape_name)
    if skip:
        rec["skip"] = skip
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_kind}.json"), "w") as f:
            json.dump(rec, f, indent=2)
        return rec
    t0 = time.time()
    try:
        with shd.activate(mesh), mesh:
            cell = input_specs(cfg, shape, mesh)
            jfn = jax.jit(
                cell.fn,
                in_shardings=cell.in_shardings,
                donate_argnums=cell.donate_argnums,
            )
            lowered = jfn.lower(*cell.args)
            rec["lower_s"] = round(time.time() - t0, 2)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 2)
            mem = compiled.memory_analysis()
            print(mem)   # proves it fits
            cost_list = compiled.cost_analysis()
            cost = cost_list[0] if isinstance(cost_list, (list, tuple)) else cost_list
            print({k: cost[k] for k in ("flops", "bytes accessed") if k in cost})
            hlo = compiled.as_text()
            # Exact static counts (XLA's cost_analysis counts loop bodies once
            # — hlo_counter multiplies by the known trip counts).
            counts = hlo_counter.analyze(hlo)
            link_bw = DCI_BW if multi else ICI_BW
            compute_s = counts.flops / PEAK_FLOPS_BF16
            memory_s = counts.bytes / HBM_BW
            collective_s = counts.coll_total / link_bw
            dominant = max(
                [("compute", compute_s), ("memory", memory_s), ("collective", collective_s)],
                key=lambda kv: kv[1],
            )[0]
            # MODEL_FLOPS: 6·N_active·tokens (train) / 2·N_active·tokens (fwd)
            n_active = cfg.active_param_count()
            tokens = shape.global_batch * (1 if shape.kind == "decode" else shape.seq_len)
            mult = 6 if shape.kind == "train" else 2
            model_flops_dev = mult * n_active * tokens / chips
            rec.update(
                ok=True,
                meta=cell.meta,
                memory={
                    k: int(getattr(mem, k))
                    for k in (
                        "argument_size_in_bytes",
                        "output_size_in_bytes",
                        "temp_size_in_bytes",
                        "generated_code_size_in_bytes",
                    )
                    if hasattr(mem, k)
                },
                xla_cost={k: float(cost[k]) for k in ("flops", "bytes accessed") if k in cost},
                counted={
                    "flops": counts.flops,
                    "hbm_bytes": counts.bytes,
                    "collective_bytes": counts.coll,
                    "collective_calls": counts.coll_calls,
                },
                model_flops_per_device=model_flops_dev,
                useful_ratio=model_flops_dev / max(counts.flops, 1.0),
                roofline={
                    "compute_s": compute_s,
                    "memory_s": memory_s,
                    "collective_s": collective_s,
                    "dominant": dominant,
                },
            )
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 2)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_kind}.json"), "w") as f:
        json.dump(rec, f, indent=2)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_NAMES + ["all"], nargs="?")
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + ["all"], nargs="?")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default=REPORT_DIR)
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = ARCH_NAMES if args.arch in (None, "all") else [args.arch]
    shapes = list(SHAPES) if args.shape in (None, "all") else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    results = []
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                out_file = os.path.join(args.out, f"{arch}__{shape}__{mesh_kind}.json")
                if args.skip_existing and os.path.exists(out_file):
                    with open(out_file) as f:
                        rec = json.load(f)
                    if rec.get("ok") or rec.get("skip"):
                        results.append(rec)
                        print(f"[cached] {arch} × {shape} × {mesh_kind}")
                        continue
                print(f"=== {arch} × {shape} × {mesh_kind}", flush=True)
                rec = run_cell(arch, shape, mesh_kind, args.out)
                status = "OK" if rec.get("ok") else rec.get("skip") or "FAIL"
                print(
                    f"--> {status}  lower={rec.get('lower_s', '-')}s "
                    f"compile={rec.get('compile_s', '-')}s "
                    f"dominant={rec.get('roofline', {}).get('dominant', '-')}",
                    flush=True,
                )
                if not rec.get("ok") and not rec.get("skip"):
                    print(rec.get("error"), flush=True)
                results.append(rec)

    ok = sum(1 for r in results if r.get("ok"))
    skipped = sum(1 for r in results if r.get("skip"))
    failed = [r for r in results if not r.get("ok") and not r.get("skip")]
    print(f"\n=== dry-run summary: {ok} ok / {skipped} skipped / {len(failed)} failed")
    for r in failed:
        print(f"  FAIL {r['arch']} × {r['shape']} × {r['mesh']}: {r.get('error')}")
    return 0 if not failed else 1


if __name__ == "__main__":
    raise SystemExit(main())
