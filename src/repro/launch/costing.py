"""Bandwidth-bound collective time from per-device collective bytes.

``_STREAM_TILE`` is the tile that ``comm_time_model``'s streamed schedule
should be given: the sync layer's own default.
"""
from __future__ import annotations

from typing import Dict

from repro.comm.topology import DEFAULT_TILE_BYTES as _STREAM_TILE  # noqa: F401


def comm_time_model(measures: Dict[str, float], topology=None,
                    tile_bytes: int = 0, faults=None) -> Dict[str, float]:
    """Bandwidth-bound collective wall-clock from per-device collective bytes
    (``coll_total`` and ``coll_interpod``, as ``launch/hlo_analysis.py``
    counts them).

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
