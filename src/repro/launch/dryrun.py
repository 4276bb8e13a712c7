"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combination.

Proves the distribution config is coherent without hardware: jit(...).lower()
against ShapeDtypeStruct inputs, .compile() under the production mesh, then
record memory_analysis / cost_analysis / collective payloads for §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-4b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all --multi-pod both
"""
# The VERY FIRST lines — before ANY other import, jax locks device count on
# first init.  512 placeholder host devices cover both production meshes.
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import sys
import traceback

from repro.configs.base import INPUT_SHAPES, get_config, list_configs
from repro.launch import hlo_analysis as hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.sharded import (
    build_decode_lowering, build_prefill_lowering, build_train_lowering)
from repro.launch.specs import skip_reason
from repro.obs.trace import wall_s
from repro.utils.logging import get_logger

log = get_logger("dryrun")


def run_one(arch: str, shape_name: str, multi_pod: bool, sync_mode: str = "dense",
            compressor: str = "qsgd", remat: str = "full",
            compile_only: bool = False) -> dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16", "sync": sync_mode}

    reason = skip_reason(cfg, shape_name)
    if reason:
        rec.update(status="skipped", reason=reason)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = wall_s()
    try:
        if shape.kind == "train":
            lowered = build_train_lowering(cfg, mesh, shape, sync_mode, compressor,
                                           remat=remat)
        elif shape.kind == "prefill":
            lowered = build_prefill_lowering(cfg, mesh, shape, remat=remat)
        else:
            lowered = build_decode_lowering(cfg, mesh, shape)
        rec["lower_s"] = round(wall_s() - t0, 2)
        t1 = wall_s()
        compiled = lowered.compile()
        rec["compile_s"] = round(wall_s() - t1, 2)
        rec["memory"] = hlo.memory_dict(compiled)
        rec["cost"] = hlo.cost_dict(compiled)
        rec["collectives"] = hlo.collective_bytes(compiled.as_text()).as_dict()
        rec["status"] = "ok"
        print(f"memory_analysis: {rec['memory']}")
        print(f"cost_analysis flops={rec['cost'].get('flops')} "
              f"bytes={rec['cost'].get('bytes accessed')}")
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--sync", default="dense",
                    choices=["dense", "efbv", "ef21", "diana", "hier", "local"])
    ap.add_argument("--compressor", default="qsgd")
    ap.add_argument("--remat", default="full", choices=["none", "dots", "full"])
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    archs = list_configs() if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    pods = {"single": [False], "multi": [True], "both": [False, True]}[args.multi_pod]

    os.makedirs(args.out, exist_ok=True)
    results = []
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                tag = f"{arch}__{shape}__{'mp' if mp else 'sp'}__{args.sync}"
                log.info("dry-run %s", tag)
                rec = run_one(arch, shape, mp, args.sync, args.compressor, args.remat)
                results.append(rec)
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(rec, f, indent=2)
                log.info("  -> %s (lower %.1fs compile %.1fs)", rec["status"],
                         rec.get("lower_s", 0), rec.get("compile_s", 0))
                if rec["status"] == "error":
                    log.info("  error: %s", rec["error"])
    ok = sum(r["status"] == "ok" for r in results)
    sk = sum(r["status"] == "skipped" for r in results)
    n_err = len(results) - ok - sk
    log.info("done: %d ok, %d skipped, %d error of %d", ok, sk, n_err,
             len(results))
    return results


if __name__ == "__main__":
    # every record is written; the exit code says whether any failed
    sys.exit(1 if any(r["status"] == "error" for r in main()) else 0)
