"""Pallas TPU kernels for wire-format packing (repro.comm codecs).

Two kernel families back the payload codecs:

  * ``pack_mask_2d`` / ``unpack_mask_2d`` — 1-bit mask <-> uint32 words.
    Sparsifier payloads ship a presence bitmap (1 bit per coordinate) next to
    the kept values; packing 32 mask bits into one word is a pure VPU
    reduction.  Layout: the (32, C) input block is reduced along the sublane
    axis — bit j of word [0, c] is mask[j, c] — so the word stream for a flat
    vector uses a stride-W bit order (see kernels/ops.pack_bits for the host
    view).  Lanes stay 128-aligned; no in-kernel reshapes.

  * ``quant_pack_2d`` / ``unpack_dequant_2d`` — fused blockwise absmax
    quantize straight to the int8 wire plane + per-block fp32 scales, and the
    inverse.  Unlike kernels/quant8 (quantize-*dequantize*, the on-chip
    compressor carrier) these emit the actual transport buffers: one VMEM pass
    produces what goes on the wire, instead of quantize -> dequantize ->
    re-quantize on the host.

Pure-jnp oracles live in kernels/ref.py; whether a kernel compiles or runs in
the interpreter is decided by kernels/backend.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve
from repro.kernels.quant8 import QBLOCK, TILE_ROWS

PACK_BITS = 32      # bits per packed word (uint32)
PACK_LANES = 128    # lane tile for the word axis


# ---------------------------------------------------------------------------
# mask bitpack
# ---------------------------------------------------------------------------
def _pack_kernel(mask_ref, out_ref):
    # Mosaic has no reduction over unsigned integers: sum in int32.  The 32
    # shifted bits are disjoint, so the wrapping int32 sum has exactly the
    # bit pattern of their OR (bit 31 lands in the sign bit).
    bits = mask_ref[...].astype(jnp.int32)
    shifts = jax.lax.broadcasted_iota(jnp.int32, bits.shape, dimension=0)
    words = jnp.sum(bits << shifts, axis=0, keepdims=True)
    out_ref[...] = jax.lax.bitcast_convert_type(words, jnp.uint32)


def _unpack_kernel(words_ref, out_ref):
    words = words_ref[...]
    shifts = jax.lax.broadcasted_iota(jnp.uint32, out_ref.shape, dimension=0)
    out_ref[...] = ((jnp.broadcast_to(words, out_ref.shape) >> shifts)
                    & jnp.uint32(1)).astype(jnp.uint32)


def pack_mask_2d(mask2d: jax.Array, interpret: Optional[bool] = None) -> jax.Array:
    """(32, C) {0,1} mask -> (1, C) uint32 words; C % PACK_LANES == 0."""
    rows, c = mask2d.shape
    assert rows == PACK_BITS and c % PACK_LANES == 0, (mask2d.shape,)
    grid = (c // PACK_LANES,)
    return pl.pallas_call(
        _pack_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((PACK_BITS, PACK_LANES), lambda j: (0, j))],
        out_specs=pl.BlockSpec((1, PACK_LANES), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, c), jnp.uint32),
        interpret=resolve(interpret),
    )(mask2d.astype(jnp.uint32))


def unpack_mask_2d(words2d: jax.Array, interpret: Optional[bool] = None) -> jax.Array:
    """(1, C) uint32 words -> (32, C) {0,1} uint32 mask."""
    one, c = words2d.shape
    assert one == 1 and c % PACK_LANES == 0, (words2d.shape,)
    grid = (c // PACK_LANES,)
    return pl.pallas_call(
        _unpack_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, PACK_LANES), lambda j: (0, j))],
        out_specs=pl.BlockSpec((PACK_BITS, PACK_LANES), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((PACK_BITS, c), jnp.uint32),
        interpret=resolve(interpret),
    )(words2d)


# ---------------------------------------------------------------------------
# fused quantize-pack / unpack-dequantize
# ---------------------------------------------------------------------------
def _quant_pack_kernel(x_ref, noise_ref, q_ref, scale_ref, *, s_levels: int):
    x = x_ref[...].astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=1, keepdims=True) / s_levels
    scale = jnp.where(scale == 0.0, 1.0, scale)
    q = jnp.floor(x / scale + noise_ref[...])      # noise in [0,1): stochastic
    q = jnp.clip(q, -s_levels, s_levels)
    q_ref[...] = q.astype(jnp.int8)
    scale_ref[...] = scale


def _unpack_dequant_kernel(q_ref, scale_ref, out_ref):
    out_ref[...] = (q_ref[...].astype(jnp.float32) * scale_ref[...]).astype(
        out_ref.dtype)


def quant_pack_2d(x2d: jax.Array, noise2d: jax.Array, bits: int = 8,
                  interpret: Optional[bool] = None):
    """(rows, QBLOCK) -> (int8 plane (rows, QBLOCK), fp32 scales (rows, 1)).

    Same math as quant8.quant_dequant_2d but emits the wire planes; the two
    kernels agree bit-for-bit (q * scale reproduces the dequantized carrier).
    """
    rows, qb = x2d.shape
    assert qb == QBLOCK and rows % TILE_ROWS == 0, (x2d.shape,)
    s = 2 ** (bits - 1) - 1
    grid = (rows // TILE_ROWS,)
    return pl.pallas_call(
        functools.partial(_quant_pack_kernel, s_levels=s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_ROWS, QBLOCK), lambda i: (i, 0)),
            pl.BlockSpec((TILE_ROWS, QBLOCK), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((TILE_ROWS, QBLOCK), lambda i: (i, 0)),
            pl.BlockSpec((TILE_ROWS, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, qb), jnp.int8),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=resolve(interpret),
    )(x2d, noise2d)


def unpack_dequant_2d(q2d: jax.Array, scales: jax.Array, out_dtype=jnp.float32,
                      interpret: Optional[bool] = None) -> jax.Array:
    """Inverse of quant_pack_2d: int8 plane + (rows, 1) scales -> dense."""
    rows, qb = q2d.shape
    assert qb == QBLOCK and rows % TILE_ROWS == 0, (q2d.shape,)
    assert scales.shape == (rows, 1), (scales.shape,)
    grid = (rows // TILE_ROWS,)
    return pl.pallas_call(
        _unpack_dequant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_ROWS, QBLOCK), lambda i: (i, 0)),
            pl.BlockSpec((TILE_ROWS, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_ROWS, QBLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, qb), out_dtype),
        interpret=resolve(interpret),
    )(q2d, scales)
