"""Pallas TPU kernel: fused pruning-score + threshold-mask application.

SymWanda's pruning pass scores every weight and masks below a per-output
threshold.  The naive chain materializes the full (d_in, d_out) score matrix
in HBM (score -> top-k threshold -> compare -> mask): three extra HBM passes
over a matrix the size of the weights.  The fused kernel recomputes the score
in VMEM from O(d_in + d_out) statistics and applies the mask in the same tile
pass — weights are read once and written once.

Score modes (static):
  wanda:    s_ij = |w_ij| * xnorm_i
  ria:      s_ij = (|w_ij|/rowsum_i + |w_ij|/colsum_j) * xnorm_i^alpha
  symwanda: s_ij = beta * |w_ij| xnorm_i / mu_in + (1-beta) |w_ij| ynorm_j / mu_out

Per-output thresholds tau_j are computed once outside (global top-k over a
cheap column pass) and broadcast into the kernel.
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


def _score(w, xnorm_col, stats, mode: str, alpha: float, beta: float):
    aw = jnp.abs(w.astype(jnp.float32))
    if mode == "wanda":
        return aw * xnorm_col
    if mode == "ria":
        rowsum, colsum = stats
        return (aw / rowsum + aw / colsum) * (xnorm_col ** alpha)
    if mode == "symwanda":
        ynorm_row, mu_in, mu_out = stats
        return beta * aw * xnorm_col / mu_in + (1.0 - beta) * aw * ynorm_row / mu_out
    raise ValueError(mode)


def _wanda_kernel(w_ref, xn_ref, tau_ref, rs_ref, cs_ref, out_ref, mask_ref,
                  *, mode: str, alpha: float, beta: float):
    w = w_ref[...]
    xn = xn_ref[...]           # (1, TILE_R) input-channel norms for this row tile
    tau = tau_ref[...]         # (1, TILE_C) per-output thresholds
    if mode == "ria":
        stats = (rs_ref[...].T, cs_ref[...])     # rowsum (TILE_R,1), colsum (1,TILE_C)
    elif mode == "symwanda":
        stats = (cs_ref[...], rs_ref[0, 0], rs_ref[0, 1])
    else:
        stats = None
    s = _score(w, xn.T, stats, mode, alpha, beta)
    keep = (s >= tau).astype(w.dtype)
    mask_ref[...] = keep
    out_ref[...] = w * keep


def wanda_prune_2d(w: jax.Array, xnorm: jax.Array, tau: jax.Array,
                   mode: str = "wanda", alpha: float = 0.5, beta: float = 0.5,
                   rowsum: jax.Array = None, colsum: jax.Array = None,
                   ynorm: jax.Array = None, interpret: Optional[bool] = None):
    """w (d_in, d_out); xnorm (d_in,); tau (d_out,). RIA: rowsum (d_in,),
    colsum (d_out,). SymWanda: ynorm (d_out,) + normalizers packed by ops.py.
    Returns (pruned w, mask)."""
    d_in, d_out = w.shape
    assert d_in % TILE_R == 0 and d_out % TILE_C == 0
    grid = (d_in // TILE_R, d_out // TILE_C)
    wspec = pl.BlockSpec((TILE_R, TILE_C), lambda i, j: (i, j))
    rowvec = pl.BlockSpec((1, TILE_R), lambda i, j: (0, i))
    colvec = pl.BlockSpec((1, TILE_C), lambda i, j: (0, j))

    if mode == "wanda":
        rs = jnp.zeros((1, d_in), jnp.float32)
        cs = jnp.zeros((1, d_out), jnp.float32)
        rs_spec, cs_spec = rowvec, colvec
    elif mode == "ria":
        rs = rowsum.reshape(1, d_in).astype(jnp.float32)
        cs = colsum.reshape(1, d_out).astype(jnp.float32)
        rs_spec, cs_spec = rowvec, colvec
    elif mode == "symwanda":
        # rs carries the two scalar normalizers; cs carries ynorm per output
        rs = jnp.zeros((1, 128), jnp.float32).at[0, 0].set(rowsum).at[0, 1].set(colsum)
        cs = ynorm.reshape(1, d_out).astype(jnp.float32)
        rs_spec = pl.BlockSpec((1, 128), lambda i, j: (0, 0))
        cs_spec = colvec
    else:
        raise ValueError(mode)

    return pl.pallas_call(
        functools.partial(_wanda_kernel, mode=mode, alpha=alpha, beta=beta),
        grid=grid,
        in_specs=[wspec, rowvec, colvec, rs_spec, cs_spec],
        out_specs=[wspec, wspec],
        out_shape=[
            jax.ShapeDtypeStruct(w.shape, w.dtype),
            jax.ShapeDtypeStruct(w.shape, w.dtype),
        ],
        interpret=resolve(interpret),
    )(w, xnorm.reshape(1, d_in).astype(jnp.float32),
      tau.reshape(1, d_out).astype(jnp.float32), rs, cs)
