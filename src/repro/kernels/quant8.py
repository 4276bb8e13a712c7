"""Pallas TPU kernel: block-wise absmax int-s quantize + dequantize (fused).

This is the compute hot-spot of the quantization compressor (Ch. 2): every
compressed sync quantizes the full gradient delta.  Fusing quantize+dequantize
keeps the tensor in VMEM for one pass (read once, write once) instead of the
three HBM round-trips of the naive absmax -> scale -> round chain.

Layout: the flat tensor is viewed as (rows, QBLOCK) where QBLOCK is the
quantization block (one scale per row).  The Pallas grid tiles rows; each tile
is (TILE_ROWS, QBLOCK) in VMEM — QBLOCK is chosen 128-lane aligned.

Stochastic rounding takes pre-generated uniform noise as a kernel input (an
explicit functional PRNG keeps the kernel portable and the oracle exact).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve

TILE_ROWS = 8
QBLOCK = 512  # quantization block size (multiple of 128 lanes)


def _quant_kernel(x_ref, noise_ref, out_ref, *, s_levels: int):
    x = x_ref[...].astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=1, keepdims=True) / s_levels
    scale = jnp.where(scale == 0.0, 1.0, scale)
    y = x / scale
    q = jnp.floor(y + noise_ref[...])          # noise in [0,1): stochastic round
    q = jnp.clip(q, -s_levels, s_levels)
    out_ref[...] = (q * scale).astype(out_ref.dtype)


def quant_dequant_2d(x2d: jax.Array, noise2d: jax.Array, bits: int = 8,
                     interpret: Optional[bool] = None) -> jax.Array:
    """x2d, noise2d: (rows, QBLOCK). rows must be a multiple of TILE_ROWS."""
    rows, qb = x2d.shape
    assert qb == QBLOCK and rows % TILE_ROWS == 0, (x2d.shape,)
    s = 2 ** (bits - 1) - 1
    grid = (rows // TILE_ROWS,)
    return pl.pallas_call(
        functools.partial(_quant_kernel, s_levels=s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_ROWS, QBLOCK), lambda i: (i, 0)),
            pl.BlockSpec((TILE_ROWS, QBLOCK), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_ROWS, QBLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        interpret=resolve(interpret),
    )(x2d, noise2d)
