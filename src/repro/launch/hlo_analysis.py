"""Roofline-term extraction from compiled XLA artifacts.

``cost_analysis`` supplies HLO_FLOPs and HLO bytes; collective traffic is NOT
in cost_analysis, so we parse the post-SPMD optimized HLO text and sum the
bytes each collective moves per participating device:

    all-reduce          operand bytes  (ring: ~2x(g-1)/g x operand; we report
                        operand bytes as the canonical payload)
    all-gather          result/group   (each device contributes its shard)
    reduce-scatter      operand/group  x (group-1) ~ operand bytes scattered;
                        we count operand bytes / group x (group - 1)
    all-to-all          operand bytes x (group-1)/group
    collective-permute  operand bytes

Payload bytes are per-device; multiplying by the link count is left to the
caller.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(type_str: str) -> int:
    """'bf16[2048,4096]' -> bytes. Tuple types: sum of components."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = re.search(r"replica_groups=\{\{([^}]*)\}", line)
    if m:
        return max(1, len(m.group(1).split(",")))
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:  # iota format [ngroups, group_size]
        return max(1, int(m.group(2)))
    return 1


def _crosses_pod(line: str, pod_size: int = 256) -> bool:
    """True if any replica group spans the pod boundary (device ids on both
    sides of ``pod_size``) — i.e. the collective uses the slow inter-pod
    links. Unknown formats default to False (intra)."""
    m = re.search(r"replica_groups=\{\{([^=]*?)\}\}", line)
    if m:
        for grp in m.group(1).split("},{"):
            ids = [int(t) for t in grp.split(",") if t.strip().isdigit()]
            if ids and min(ids) < pod_size <= max(ids):
                return True
        return False
    # iota format [ngroups,gsize]<=[...] : a group crosses the pod iff its
    # id-stride pattern spans >= pod_size; conservative check via T() perm
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\]", line)
    if m:
        ngroups, gsize = int(m.group(1)), int(m.group(2))
        # contiguous iota: group g = [g*gsize, (g+1)*gsize) — crosses only if
        # gsize > pod_size; transposed iota (T(...)) strides across pods
        if "T(" in line[m.start():m.end() + 20]:
            return gsize > 1 and ngroups * gsize > pod_size
        return gsize > pod_size
    return False


@dataclass
class CollectiveStats:
    bytes_by_kind: Dict[str, float] = field(default_factory=lambda: defaultdict(float))
    count_by_kind: Dict[str, int] = field(default_factory=lambda: defaultdict(int))
    inter_pod_bytes: float = 0.0

    @property
    def total_bytes(self) -> float:
        return float(sum(self.bytes_by_kind.values()))

    def as_dict(self) -> dict:
        return {
            "total_bytes": self.total_bytes,
            "inter_pod_bytes": float(self.inter_pod_bytes),
            "by_kind": {k: float(v) for k, v in self.bytes_by_kind.items()},
            "counts": dict(self.count_by_kind),
        }


def collective_bytes(hlo_text: str) -> CollectiveStats:
    """Parse optimized (post-SPMD) HLO text, sum per-device collective payload."""
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        ls = line.strip()
        # result line looks like: %x = bf16[..] all-reduce(...), replica_groups=..
        m = re.search(r"=\s*((?:\([^)]*\))|(?:[\w\[\],]+))\s+(" + "|".join(_COLLECTIVES) + r")[\s(.-]", ls)
        if not m:
            continue
        rtype, kind = m.group(1), m.group(2)
        if "-start" in ls and f"{kind}-start" not in ls:
            pass
        if f"{kind}-done" in ls:
            continue  # count the -start (or sync op), not the done
        rbytes = _shape_bytes(rtype)
        g = _group_size(ls)
        if kind == "all-reduce":
            payload = rbytes
        elif kind == "all-gather":
            payload = rbytes / max(g, 1)
        elif kind == "reduce-scatter":
            payload = rbytes * (g - 1) / max(g, 1) if g > 1 else rbytes
        elif kind == "all-to-all":
            payload = rbytes * (g - 1) / max(g, 1)
        else:  # collective-permute
            payload = rbytes
        stats.bytes_by_kind[kind] += payload
        stats.count_by_kind[kind] += 1
        if _crosses_pod(ls):
            stats.inter_pod_bytes += payload
    return stats


def cost_dict(compiled) -> dict:
    """Normalize compiled.cost_analysis() across jax versions."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return {k: float(v) for k, v in ca.items() if isinstance(v, (int, float))}


def memory_dict(compiled) -> dict:
    ma = compiled.memory_analysis()
    out = {}
    for k in ("generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "alias_size_in_bytes",
              "temp_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out
