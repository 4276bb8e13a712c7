"""Trace-time sharding hooks.

The step builders (training/steps.py) are mesh-agnostic; the launcher installs
PartitionSpec pytrees here before lowering so internal tensors that XLA's
propagation gets wrong are pinned explicitly:

  * gradients — FSDP backward leaves weight grads replicated after the
    all-gathered matmul; without a constraint the f32 optimizer math then
    runs (and allocates) at full size. Constraining grads to the param spec
    turns that into the reduce-scatter + sharded-update pattern (ZeRO).
"""
from __future__ import annotations

from typing import Optional

import jax

_GRAD_SPECS = None
_MOE_SPECS = None


def set_grad_specs(specs) -> None:
    global _GRAD_SPECS
    _GRAD_SPECS = specs


def constrain_grads(grads):
    if _GRAD_SPECS is None:
        return grads
    return jax.tree_util.tree_map(
        lambda g, s: jax.lax.with_sharding_constraint(g, s), grads, _GRAD_SPECS)


def set_moe_specs(specs: Optional[dict]) -> None:
    """{'impl': 'shardmap'|'scatter', 'mesh': Mesh, 'data_axes': tuple, plus
    optional 'tokens'/'expanded'/'buf' PartitionSpecs for the scatter path}.
    Installed by the launcher; None disables (tests/CPU)."""
    global _MOE_SPECS
    _MOE_SPECS = specs


def get_moe_specs() -> Optional[dict]:
    return _MOE_SPECS


def constrain_moe(name: str, x):
    if not _MOE_SPECS or name not in _MOE_SPECS:
        return x
    spec = _MOE_SPECS[name]
    sizes = dict(_MOE_SPECS["mesh"].shape)

    def ok(dim, ax):
        if ax is None:
            return None
        size = 1
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            size *= sizes.get(a, 10**9)
        return ax if (dim % size == 0 and dim >= size) else None

    from jax.sharding import NamedSharding, PartitionSpec as P
    fixed = P(*[ok(d, a) for d, a in zip(x.shape, tuple(spec) + (None,) * x.ndim)])
    # NamedSharding works with or without an ambient mesh context
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_MOE_SPECS["mesh"], fixed))
