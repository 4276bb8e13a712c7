"""§Perf hillclimb harness: lower a (arch, shape) pair under a named variant,
report the loop-corrected roofline terms + memory against the baseline.

Variants (composable via comma list):
  banded      — banded flash attention: SWA/chunked layers skip masked KV
                blocks (exact numerics; cuts attention FLOPs from O(S^2) to
                O(S*window))
  ssd_heads   — shard SSD head dim over 'model' inside mamba blocks (cuts the
                (B,K,Q,Q,H) intra-chunk tensors 16x)
  sync_hier   — Cohort-Squeeze pod-level sync (paper technique): dense
                intra-pod, EF21-compressed inter-pod every sync_period steps
  sync_efbv   — EF-BV compressed gradient sync on the data axis
  moe_quant   — int8 token gather + bf16 psum in the shard_map MoE
  moe_a2a     — all-to-all expert dispatch: tokens stay d-sharded, only
                routed rows travel (~E/(K*cf) x less MoE traffic)
  no_tp       — pure-FSDP sharding (no tensor parallelism): for small models
                whose TP activation all-reduces dwarf the weights
  accum2x     — double grad-accum microbatching (memory vs collectives trade)

Usage:
  PYTHONPATH=src python -m repro.launch.perf --arch h2o-danube-1.8b \
      --shape prefill_32k --variants banded
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import json

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9


def apply_variants(variants, mesh, cfg):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models import attention as attn_lib
    from repro.sharding.context import set_named_specs
    from repro.sharding.rules import data_axes

    daxes = data_axes(mesh)
    dax = daxes if len(daxes) > 1 else daxes[0]
    sync = "dense"
    extra = {}
    if "banded" in variants:
        attn_lib.BANDED = True
    if "ssd_heads" in variants and cfg.mamba is not None:
        set_named_specs({
            "ssd_x": NamedSharding(mesh, P(dax, None, "model", None)),
            "ssd_dt": NamedSharding(mesh, P(dax, None, "model")),
        })
    if "no_tp" in variants:
        from repro.sharding import rules as _rules
        _rules.NO_TP = True
    if "moe_a2a" in variants:
        from repro.sharding.context import set_moe_impl_override
        set_moe_impl_override("alltoall")
    if "moe_quant" in variants:
        from repro.sharding.context import set_moe_gather_quant
        set_moe_gather_quant(True)
    if "sync_hier" in variants:
        sync = "hier"
    if "sync_efbv" in variants:
        sync = "efbv"
    if "accum2x" in variants:
        extra["accum_mult"] = 2
    return sync, extra


def reset_variants():
    from repro.models import attention as attn_lib
    from repro.sharding.context import set_named_specs

    attn_lib.BANDED = False
    set_named_specs(None)
    from repro.sharding.context import set_moe_gather_quant
    set_moe_gather_quant(False)
    from repro.sharding import rules as _rules
    _rules.NO_TP = False
    from repro.sharding.context import set_moe_impl_override
    set_moe_impl_override(None)


def measure(arch, shape_name, variants, multi_pod=False):
    import jax
    from repro.configs.base import INPUT_SHAPES, get_config
    from repro.launch import sharded as dr
    from repro.launch.costing import corrected_costs, model_flops
    from repro.launch.mesh import make_production_mesh
    from repro.launch import hlo_analysis as hlo
    from repro.obs import trace as obs_trace

    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    sync, extra = apply_variants(variants, mesh, cfg)
    try:
        # full lowering -> memory proof (each phase is a flight-recorder span
        # when tracing is on, so a traced hillclimb shows where compiles go)
        t0 = obs_trace.wall_s()
        with obs_trace.span("perf/lower", arch=arch, shape=shape_name,
                            sync=sync):
            if shape.kind == "train":
                ga = None
                if extra.get("accum_mult"):
                    ga = dr.auto_grad_accum(cfg, shape, 32 if multi_pod else 16) * extra["accum_mult"]
                low = dr.build_train_lowering(cfg, mesh, shape, sync_mode=sync, grad_accum=ga)
            elif shape.kind == "prefill":
                low = dr.build_prefill_lowering(cfg, mesh, shape)
            else:
                low = dr.build_decode_lowering(cfg, mesh, shape)
        with obs_trace.span("perf/compile", arch=arch, shape=shape_name):
            comp = low.compile()
        with obs_trace.span("perf/memory"):
            mem = hlo.memory_dict(comp)
        # corrected costs (re-applies the same variant flags inside)
        with obs_trace.span("perf/corrected_costs"):
            cc = corrected_costs(cfg, mesh, shape_name, sync_mode=sync)
        c = cc["corrected"]
        terms = {
            "compute_s": c.get("flops", 0.0) / PEAK_FLOPS,
            "memory_s": c.get("bytes", 0.0) / HBM_BW,
            "collective_s": c.get("coll_total", 0.0) / ICI_BW,
            "interpod_s": c.get("coll_interpod", 0.0) / (ICI_BW / 4),
        }
        mf = model_flops(cfg, shape_name)["model_flops"]
        n_chips = 512 if multi_pod else 256
        return {
            "arch": arch, "shape": shape_name, "variants": variants,
            "sync": sync, "mesh": "2x16x16" if multi_pod else "16x16",
            "terms_s": terms,
            "dominant": max((k for k in terms if k != "interpod_s"),
                            key=lambda k: terms[k]),
            "useful_ratio": mf / (c.get("flops", 1) * n_chips),
            "mem_gb": {k: v / 1e9 for k, v in mem.items() if "size" in k},
            "compile_s": round(obs_trace.wall_s() - t0, 1),
        }
    finally:
        reset_variants()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variants", default="", help="comma list; empty = baseline")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    variants = [v for v in args.variants.split(",") if v]
    from repro.obs import trace as obs_trace

    rec = measure(args.arch, args.shape, variants, args.multi_pod)
    if obs_trace.enabled():
        # every perf row carries its trace file (REPRO_TRACE=1)
        obs_trace.set_meta(label=f"perf_{args.arch}_{args.shape}",
                           variants=",".join(variants))
        rec["trace"] = obs_trace.export_jsonl(
            f"TRACE_perf_{args.arch}_{args.shape}.jsonl")
    print(json.dumps(rec, indent=2))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rec, f, indent=2)


if __name__ == "__main__":
    main()
