"""Sharding rules engine: param-path patterns -> PartitionSpec.

The production mesh is (data=16, model=16) single-pod or (pod=2, data=16,
model=16) multi-pod.  Rules follow Megatron-style tensor parallelism on the
``model`` axis (FFN hidden, attention projections, vocab, MoE expert axis)
with batch data-parallel over (pod, data).  A divisibility check drops an
axis when the dimension is smaller than the mesh axis (e.g. batch=1 decode);
GSPMD tolerates uneven sharding, but dims < axis size would be pure padding.

Every rule is a (path regex, spec-for-trailing-dims) pair; leading stack dims
added by the layer-scan (n_periods) or by local-training replicas are handled
by prepending.
"""
from __future__ import annotations

import re
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# data-parallel axes: ("pod", "data") on the multi-pod mesh, ("data",) else
def data_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


DATA_AXES = data_axes  # alias


# (regex on '/'-joined path, trailing-dims partition tuple)
_PARAM_RULES = [
    # embedding table sharded on the FEATURE dim: a gather whose rows are
    # unsharded partitions trivially (each model shard gathers its d-slice);
    # vocab-sharded tables trip GSPMD's gather partitioning inside scan+remat
    (r"embed/tok$", (None, "model")),
    (r"embed/unembed$", (None, "model")),
    (r"(attn|xattn)/wq$", (None, "model")),
    (r"(attn|xattn)/wk$", (None, "model")),
    (r"(attn|xattn)/wv$", (None, "model")),
    (r"(attn|xattn)/wo$", ("model", None)),
    (r"(attn|xattn)/b[qkv]$", ("model",)),
    (r"(mlp|shared)/w_(in|gate)$", (None, "model")),
    (r"(mlp|shared)/w_out$", ("model", None)),
    (r"moe/router$", (None, None)),
    (r"moe/w_(in|gate)$", ("model", None, None)),   # expert parallel
    (r"moe/w_out$", ("model", None, None)),
    (r"mamba/in_proj$", (None, "model")),
    (r"mamba/conv_[wb]$", (None,)),                  # small; replicate
    (r"mamba/(a_log|dt_bias|D)$", (None,)),
    (r"mamba/out_proj$", ("model", None)),
    (r"vision_proj$", (None, "model")),
    (r"norm", (None,)),
    (r"(final_norm|norm1|norm2|norm_x)/scale$", (None,)),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        else:
            parts.append(str(p))
    return "/".join(parts)


def maybe_axis(dim: int, axis: Optional[str], mesh: Mesh):
    """Drop the axis unless the dim divides evenly over the mesh axis
    (jax in/out shardings reject uneven partitions, e.g. vocab 50280 on 16)."""
    if axis is None:
        return None
    size = 1
    for a in (axis if isinstance(axis, tuple) else (axis,)):
        size *= mesh.shape[a]
    return axis if (dim >= size and dim % size == 0) else None


def _spec_for(path_s: str, shape, mesh: Mesh, extra_leading: int = 0) -> P:
    for pat, trailing in _PARAM_RULES:
        if re.search(pat, path_s):
            nt = len(trailing)
            # conv_b / scalars: trailing rule may be longer than shape
            trailing = trailing[-min(nt, len(shape) - extra_leading):]
            lead = (None,) * (len(shape) - len(trailing))
            spec = list(lead) + [
                maybe_axis(shape[len(lead) + i], ax, mesh)
                for i, ax in enumerate(trailing)
            ]
            return P(*spec)
    return P(*([None] * len(shape)))


def param_specs(params, mesh: Mesh, extra_leading: int = 0, replica_axes=None,
                fsdp_axes=None, fsdp_min_dim: int = 1024):
    """PartitionSpec pytree for a param tree (abstract or concrete).

    ``extra_leading`` dims (scan stacks) stay unsharded unless
    ``replica_axes`` names the mesh axes for the outermost leading dim
    (local-training per-group replicas).

    ``fsdp_axes`` additionally shards the first large unsharded dim of every
    weight over the given data axes (ZeRO-3 / FSDP): required for the >30B
    archs where tensor-parallel-only params exceed per-chip HBM."""

    def one(path, leaf):
        ps = _path_str(path)
        spec = list(_spec_for(ps, leaf.shape, mesh, extra_leading))
        if replica_axes is not None:
            spec[0] = replica_axes
        # embedding tables stay vocab-sharded only: FSDP over the feature dim
        # trips the SPMD partitioner on the (vocab-sharded) gather, and the
        # tables are small next to the FFN stack
        if fsdp_axes and "embed" not in ps:
            size = 1
            for a in (fsdp_axes if isinstance(fsdp_axes, tuple) else (fsdp_axes,)):
                size *= mesh.shape[a]
            start = 1 if replica_axes is not None else 0
            for i in range(start, len(spec)):
                dim = leaf.shape[i]
                if spec[i] is None and dim % size == 0 and dim >= fsdp_min_dim:
                    spec[i] = fsdp_axes
                    break
        return P(*spec)

    return jax.tree_util.tree_map_with_path(one, params)


def opt_state_specs(params, pspecs, mesh: Mesh, zero1: bool = True):
    """Specs for AdamW moments: same as the param, plus ZeRO-1 style extra
    sharding of the largest unsharded dim over the data axes (moments are
    f32 and dominate state memory on the big archs)."""
    daxes = data_axes(mesh)
    dsize = 1
    for a in daxes:
        dsize *= mesh.shape[a]

    def one(p, spec):
        spec = tuple(spec)
        if not zero1:
            return P(*spec)
        best, best_dim = None, 0
        for i, (ax, dim) in enumerate(zip(spec, p.shape)):
            if ax is None and dim % dsize == 0 and dim > best_dim:
                best, best_dim = i, dim
        if best is None:
            return P(*spec)
        new = list(spec)
        new[best] = daxes if len(daxes) > 1 else daxes[0]
        return P(*new)

    return jax.tree_util.tree_map(one, params, pspecs)


def batch_specs(batch_shapes: dict, mesh: Mesh, group_stacked: bool = False,
                axes=None):
    """Specs for input batches: leading batch dim over (pod, data)."""
    daxes = axes if axes is not None else data_axes(mesh)
    ax = daxes if len(daxes) > 1 else daxes[0]

    def one(leaf):
        if leaf.ndim == 0:
            return P()
        dims = [maybe_axis(leaf.shape[0], ax, mesh)] + [None] * (leaf.ndim - 1)
        return P(*dims)

    return jax.tree_util.tree_map(one, batch_shapes)


def cache_pspecs(cache_shapes, mesh: Mesh):
    """Decode-cache specs. Leaves are stacked (n_periods, S, B, KV, hd) for
    attention K/V (sequence-major), (n_periods, B, H, hd, N)/(n_periods, B, K-1, conv) for SSD,
    plus scalars and the enc memory (B, S, D).

    Batch shards over (pod, data) when divisible; attention cache sequence
    shards over 'model' when batch cannot absorb parallelism (long-context
    flash-decoding style) — and head/channel dims over 'model' otherwise."""
    daxes = data_axes(mesh)
    bax = daxes if len(daxes) > 1 else daxes[0]

    def one(path, leaf):
        ps = _path_str(path)
        if leaf.ndim == 0:
            return P()
        if ps.endswith("enc_memory"):
            b = maybe_axis(leaf.shape[0], bax, mesh)
            return P(b, None, maybe_axis(leaf.shape[2], "model", mesh))
        if re.search(r"/(k|v)$", ps):
            # (n_periods, S, B, KV, hd)
            _, S, B, KV, hd = leaf.shape
            b = maybe_axis(B, bax, mesh)
            s = maybe_axis(S, "model", mesh)
            return P(None, s, b, None, None)
        if ps.endswith("ssm"):
            _, B, H, hd, N = leaf.shape
            return P(None, maybe_axis(B, bax, mesh), maybe_axis(H, "model", mesh),
                     None, None)
        if ps.endswith("conv"):
            _, B, K, C = leaf.shape
            return P(None, maybe_axis(B, bax, mesh), None,
                     maybe_axis(C, "model", mesh))
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(one, cache_shapes)
