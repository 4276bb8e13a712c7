"""Pallas TPU kernel: N:M structured sparsity mask application.

Given a weight tile and a pruning-score tile, keep the N highest-scoring
entries of every contiguous group of M along the input dim and zero the rest
(SymWanda Tab. 6.6 / 2:4 semi-structured setting).  Rank-within-group is
computed with compare-count (no sort): for group element i,
    rank_i = #{k : s_k > s_i} + #{k < i : s_k == s_i}
which is exact, branch-free and vectorizes on the VPU (M is small: 4).

Tiles: (TILE_R, TILE_C) of the (d_in, d_out) weight; groups run along d_in
(rows), so TILE_R is a multiple of M.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve

TILE_R = 128
TILE_C = 128


def _nm_kernel(w_ref, s_ref, out_ref, mask_ref, *, n: int, m: int):
    w = w_ref[...]
    s = s_ref[...].astype(jnp.float32)
    R, C = s.shape
    g = s.reshape(R // m, m, C)
    # rank by compare-count with index tie-break (static M-loop, VPU-friendly)
    idx = jnp.arange(m).reshape(1, m, 1)
    ranks = []
    for i in range(m):
        si = g[:, i : i + 1, :]
        greater = jnp.sum((g > si).astype(jnp.float32), axis=1, keepdims=True)
        ties = jnp.sum(((g == si) & (idx < i)).astype(jnp.float32),
                       axis=1, keepdims=True)
        ranks.append(greater + ties)
    rank = jnp.concatenate(ranks, axis=1)
    keep = (rank < n).astype(w.dtype).reshape(R, C)
    mask_ref[...] = keep
    out_ref[...] = w * keep


def nm_prune_2d(w: jax.Array, scores: jax.Array, n: int = 2, m: int = 4,
                interpret: Optional[bool] = None):
    """w, scores: (d_in, d_out) with d_in % TILE_R == 0, d_out % TILE_C == 0.
    Returns (pruned w, mask)."""
    d_in, d_out = w.shape
    assert d_in % TILE_R == 0 and d_out % TILE_C == 0 and TILE_R % m == 0
    grid = (d_in // TILE_R, d_out // TILE_C)
    spec = pl.BlockSpec((TILE_R, TILE_C), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_nm_kernel, n=n, m=m),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct(w.shape, w.dtype),
            jax.ShapeDtypeStruct(w.shape, w.dtype),
        ],
        interpret=resolve(interpret),
    )(w, scores)
