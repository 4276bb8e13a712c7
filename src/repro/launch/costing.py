"""Loop-aware cost correction for the roofline analysis.

XLA's HLO cost analysis counts every while-loop body ONCE, ignoring trip
counts (verified: a scan of 10 matmuls reports ~1 matmul of flops).  Our
programs have exactly three loop families, all with *statically known* trip
counts, and module cost is affine in each:

  1. the layer-period scan        — trips = num_layers / P
  2. the flash-attention q x kv scans — per-instance body cost is linear in
                                     block_q * block_k; true cost ~ Sq * Sk
  3. the encoder layer scan        — trips = enc_layers (seamless; it always
                                     equals num_layers/P there, so it folds
                                     into family 1 when scaled together)
  (grad-accum microbatching is lowered at accum=1 for costing: total
   flops/bytes are chunking-invariant; the accum loop's extra per-microbatch
   gradient reduce-scatter traffic is added analytically.)

So three small lowerings solve for the affine coefficients exactly:
  A: one period,  block_k = b0      B: two periods, block_k = b0
  C: one period,  block_k = 2*b0
  per_period = B - A;  const = A - per_period;  alpha = (C - A) / (bq*b0)
  corrected  = const + n_periods * (per_period + alpha*(Sq*Sk - bq*b0))

Applied to flops, bytes-accessed and per-kind collective bytes alike.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax

from repro.configs.base import INPUT_SHAPES, ModelConfig
from repro.launch import hlo_analysis as hlo
from repro.models import attention as attn_lib
from repro.models import period_info

from repro.comm.topology import DEFAULT_TILE_BYTES as _STREAM_TILE

B0_K = 512
B0_Q = 512


def _measures(compiled) -> Dict[str, float]:
    cost = hlo.cost_dict(compiled)
    colls = hlo.collective_bytes(compiled.as_text())
    out = {"flops": cost.get("flops", 0.0),
           "bytes": cost.get("bytes accessed", 0.0),
           "coll_total": colls.total_bytes,
           "coll_interpod": colls.inter_pod_bytes}
    for k, v in colls.bytes_by_kind.items():
        out[f"coll_{k}"] = v
    return out


def _lower_variant(cfg, mesh, shape, kind: str, n_periods: int, block_k: int,
                   sync_mode: str, builders) -> Dict[str, float]:
    P, _, _, _ = period_info(cfg)
    vcfg = dataclasses.replace(
        cfg,
        num_layers=P * n_periods,
        enc_layers=(n_periods if cfg.enc_layers else 0),
    )
    from repro.models import transformer as tf_lib

    old_q, old_k = attn_lib.BLOCK_Q, attn_lib.BLOCK_K
    old_unroll = tf_lib.UNROLL_SCAN
    attn_lib.BLOCK_Q, attn_lib.BLOCK_K = B0_Q, block_k
    # unroll the 1-2 period layer loop so HLO cost analysis (while bodies
    # counted once) actually sees both periods — the B-A diff needs it
    tf_lib.UNROLL_SCAN = True
    try:
        if kind == "train":
            low = builders["train"](vcfg, mesh, shape, sync_mode=sync_mode,
                                    grad_accum=1)
        elif kind == "prefill":
            low = builders["prefill"](vcfg, mesh, shape)
        else:
            low = builders["decode"](vcfg, mesh, shape)
        return _measures(low.compile())
    finally:
        attn_lib.BLOCK_Q, attn_lib.BLOCK_K = old_q, old_k
        tf_lib.UNROLL_SCAN = old_unroll


def corrected_costs(arch_cfg: ModelConfig, mesh, shape_name: str,
                    sync_mode: str = "dense", grad_accum: int = 1) -> Dict:
    """Returns {'raw_keys': {...}, 'corrected': {...}, 'model': {...}}."""
    from repro.launch import sharded as dr

    shape = INPUT_SHAPES[shape_name]
    builders = {"train": dr.build_train_lowering,
                "prefill": dr.build_prefill_lowering,
                "decode": dr.build_decode_lowering}
    P, n_periods, pos_kinds, _ = period_info(arch_cfg)
    has_flash = shape.kind in ("train", "prefill") and any(
        k.startswith("attn") for k in pos_kinds)

    A = _lower_variant(arch_cfg, mesh, shape, shape.kind, 1, B0_K, sync_mode, builders)
    B = _lower_variant(arch_cfg, mesh, shape, shape.kind, 2, B0_K, sync_mode, builders)
    C = (_lower_variant(arch_cfg, mesh, shape, shape.kind, 1, 2 * B0_K, sync_mode,
                        builders) if has_flash else None)

    Sq = Sk = shape.seq_len
    # effective kv span per attention instance in one period: the banded
    # flash variant (attn_lib.BANDED) only visits window/chunk-reach blocks
    spans = []
    for kind in pos_kinds:
        if not kind.startswith("attn"):
            continue
        if attn_lib.BANDED and kind == "attn_swa":
            spans.append(min(Sk, arch_cfg.sliding_window + B0_K))
        elif attn_lib.BANDED and kind == "attn_chunk":
            spans.append(min(Sk, arch_cfg.attn_chunk + B0_K))
        else:
            spans.append(Sk)
    if arch_cfg.enc_layers:
        spans.extend([Sk, Sk])  # encoder self-attn + cross-attn per unit
    mean_span = (sum(spans) / len(spans)) if spans else Sk

    corrected = {}
    detail = {}
    for key in A:
        a, b = A[key], B.get(key, 0.0)
        per_period = b - a
        const = a - per_period
        corr = const + n_periods * per_period
        if C is not None:
            alpha = max(0.0, (C.get(key, 0.0) - a)) / (B0_Q * B0_K)
            corr += n_periods * alpha * max(0.0, Sq * mean_span - B0_Q * B0_K)
            detail[f"alpha_{key}"] = alpha
        corrected[key] = max(corr, a)
    return {"corrected": corrected, "variants": {"A": A, "B": B, "C": C},
            "n_periods": n_periods, "grad_accum": grad_accum,
            "mean_span": mean_span, "detail": detail,
            "comm_time": comm_time_model(corrected, tile_bytes=_STREAM_TILE)}


def comm_time_model(measures: Dict[str, float], topology=None,
                    tile_bytes: int = 0, faults=None) -> Dict[str, float]:
    """Bandwidth-bound collective wall-clock from the corrected per-device bytes.

    Splits the HLO-derived collective traffic onto the link topology: the
    inter-pod share rides the slow links, the rest the intra-pod fabric — the
    same byte split repro.comm's ledger records (ledger.crosscheck_hlo audits
    the totals).  This is a bytes/bandwidth *lower bound*: the HLO totals
    aggregate many collectives, so per-message latency and ring step counts
    are not attributable here — the per-round latency-aware model lives in
    repro.comm (Topology.allreduce_time_s / CommLedger.round_time_s).

    ``topology`` may also be a ``repro.comm.tree.TreeTopology``: the leaf
    level's fabric carries the intra share and the inter share hops every
    level above it in turn (device -> host -> region -> cloud), reported as
    one ``t_<level>_s`` term per level.

    With ``tile_bytes > 0`` the report adds ``t_comm_stream_s``: the
    hierarchical schedule streamed per tile, so each hop's transfer of tile
    k+1 overlaps the next hop's transfer of tile k (repro.comm.topology's
    pipelined model); serial t_comm_s stays the sum.

    With ``faults`` (a ``repro.faults.FaultConfig``) the report adds
    ``t_comm_degraded_s``: each hop's time inflated by the expected
    retransmission count and finished at the *order statistic* of the
    straggler max over that hop's children — capped by the per-level
    deadline — not the mean child time.
    """
    from repro.comm.topology import get_topology, pipelined_time_s
    from repro.comm.tree import TreeTopology

    topo = topology or get_topology("v5p_superpod")
    total = float(measures.get("coll_total", 0.0))
    inter = float(measures.get("coll_interpod", 0.0))
    intra = max(0.0, total - inter)
    if isinstance(topo, TreeTopology):
        t_intra = intra / (topo.levels[0].link.gbps * 1e9)
        stages = [t_intra]
        out = {"intra_bytes": intra, "inter_bytes": inter,
               f"t_{topo.levels[0].name}_s": t_intra, "topology": topo.name}
        for lev in topo.levels[1:]:
            t = inter / (lev.link.gbps * 1e9)
            out[f"t_{lev.name}_s"] = t
            stages.append(t)
        out["t_comm_s"] = sum(stages)
    else:
        t_intra = intra / (topo.intra.gbps * 1e9)
        t_inter = inter / (topo.inter.gbps * 1e9)
        stages = [t_intra, t_inter]
        out = {"intra_bytes": intra, "inter_bytes": inter,
               "t_intra_s": t_intra, "t_inter_s": t_inter,
               "t_comm_s": t_intra + t_inter, "topology": topo.name}
    if tile_bytes > 0:
        n_tiles = max(1, -(-int(total) // int(tile_bytes)))
        out["t_comm_stream_s"] = pipelined_time_s(tuple(stages), n_tiles)
        out["stream_tile_bytes"] = int(tile_bytes)
    if faults is not None and faults.enabled():
        from repro.comm.topology import straggler_level_time_s

        if isinstance(topo, TreeTopology):
            hops = [(lev.name, topo.level_faults(l, faults),
                     topo.n_children(l), t)
                    for l, (lev, t) in enumerate(zip(topo.levels, stages))]
        else:
            hops = [("intra", faults.link_faults("intra"),
                     topo.devices_per_pod, stages[0]),
                    ("inter", faults.link_faults("inter"),
                     topo.n_pods, stages[1])]
        degraded = 0.0
        for name, lf, n, t in hops:
            e_tx = faults.expected_transmissions(lf.loss_rate)
            base = (t * e_tx + faults.backoff_s * (e_tx - 1.0)
                    + lf.delay_rate * lf.delay_s)
            degraded += straggler_level_time_s(
                base, faults.straggler_rate, faults.straggler_sigma, n,
                faults.level_deadline_s(name))
        out["t_comm_degraded_s"] = degraded
    return out


def model_flops(cfg: ModelConfig, shape_name: str) -> Dict[str, float]:
    """MODEL_FLOPS: 6*N*D for training (N = active params), 2*N per token for
    decode, 2*N*D for prefill — the 'useful work' yardstick."""
    shape = INPUT_SHAPES[shape_name]
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * shape.seq_len
    if shape.kind == "train":
        return {"model_flops": 6.0 * n_active * tokens}
    if shape.kind == "prefill":
        return {"model_flops": 2.0 * n_active * tokens}
    return {"model_flops": 2.0 * n_active * shape.global_batch}
