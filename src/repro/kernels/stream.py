"""Pallas TPU kernel: double-buffered streaming quantize-pack ring.

The monolithic path (`bitpack.quant_pack_2d`) lets the pallas_call grid
machinery stage tiles; this kernel owns the data movement instead, in the
structure of the async remote-DMA ring (pallas guide §Async Remote DMA /
§Double Buffering): the flat tensor sits in HBM, a two-slot VMEM ring
copy-starts tile k+1 in while tile k is being quantize-packed, and the packed
wire planes (int8 q + fp32 scales) copy-start out while tile k+1 computes.
Every transfer is an explicit ``make_async_copy`` guarded by a per-slot DMA
semaphore — the copy-start/copy-wait skeleton a remote ring uses, with the
outbound copy landing in local HBM where a TPU deployment would
``make_async_remote_copy`` it into the neighbor's ring slot.

Pipeline per tile (slot = k % 2):

    in-DMA[k+1] start ->  wait in-DMA[k] -> wait out-DMA[k-2] (slot free)
                       -> quantize-pack tile k in VMEM -> out-DMA[k] start

Interpret mode (on the CPU) executes the same semaphore
structure serially; the pure-jnp oracle is ``ref.stream_quant_pack_ref`` and
the jit wrapper with shape plumbing is ``ops.stream_quantize_pack``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve
from repro.kernels.quant8 import QBLOCK, TILE_ROWS
from repro.obs.trace import annotate

N_SLOTS = 2  # double buffering
# Mosaic slices VMEM only along whole 128-lane tiles, so each tile's
# (TILE_ROWS, 1) scale column is staged and copied out broadcast across one
# lane tile; the wrapper keeps lane 0.
SCALE_LANES = 128


def _stream_kernel(x_hbm, noise_hbm, q_hbm, scale_hbm, *, n_tiles: int,
                   s_levels: int):
    def body(x_buf, n_buf, q_buf, s_buf, in_sems, out_sems):
        def in_dmas(slot, k):
            rows = pl.ds(k * TILE_ROWS, TILE_ROWS)
            return (pltpu.make_async_copy(x_hbm.at[rows], x_buf.at[slot],
                                          in_sems.at[slot, 0]),
                    pltpu.make_async_copy(noise_hbm.at[rows], n_buf.at[slot],
                                          in_sems.at[slot, 1]))

        def out_dmas(slot, k):
            rows = pl.ds(k * TILE_ROWS, TILE_ROWS)
            return (pltpu.make_async_copy(q_buf.at[slot], q_hbm.at[rows],
                                          out_sems.at[slot, 0]),
                    pltpu.make_async_copy(s_buf.at[slot], scale_hbm.at[rows],
                                          out_sems.at[slot, 1]))

        # fill: tile 0's inbound copies start before the loop spins up (the
        # annotate scopes are trace-time jax.named_scopes — they label the
        # ring phases in jaxpr/XLA profiles, zero runtime cost)
        with annotate("stream/ring_fill"):
            for dma in in_dmas(0, 0):
                dma.start()

        def tile_step(k, _):
            slot = jax.lax.rem(k, N_SLOTS)
            nxt = jax.lax.rem(k + 1, N_SLOTS)

            @pl.when(k + 1 < n_tiles)
            def _prefetch():
                for dma in in_dmas(nxt, k + 1):
                    dma.start()

            for dma in in_dmas(slot, k):
                dma.wait()

            @pl.when(k >= N_SLOTS)
            def _reclaim():  # slot's previous out-copy must have drained
                for dma in out_dmas(slot, k - N_SLOTS):
                    dma.wait()

            x = x_buf[slot].astype(jnp.float32)
            scale = jnp.max(jnp.abs(x), axis=1, keepdims=True) / s_levels
            scale = jnp.where(scale == 0.0, 1.0, scale)
            q = jnp.floor(x / scale + n_buf[slot])   # noise in [0,1): stochastic
            q_buf[slot] = jnp.clip(q, -s_levels, s_levels).astype(jnp.int8)
            s_buf[slot] = jnp.broadcast_to(scale, (TILE_ROWS, SCALE_LANES))

            for dma in out_dmas(slot, k):
                dma.start()
            return 0

        with annotate("stream/ring_steady"):
            jax.lax.fori_loop(0, n_tiles, tile_step, 0)

        # drain: the last min(N_SLOTS, n_tiles) out-copies are still in flight
        with annotate("stream/ring_drain"):
            for k in range(max(0, n_tiles - N_SLOTS), n_tiles):
                for dma in out_dmas(k % N_SLOTS, k):
                    dma.wait()

    pl.run_scoped(
        body,
        x_buf=pltpu.VMEM((N_SLOTS, TILE_ROWS, QBLOCK), x_hbm.dtype),
        n_buf=pltpu.VMEM((N_SLOTS, TILE_ROWS, QBLOCK), jnp.float32),
        q_buf=pltpu.VMEM((N_SLOTS, TILE_ROWS, QBLOCK), jnp.int8),
        s_buf=pltpu.VMEM((N_SLOTS, TILE_ROWS, SCALE_LANES), jnp.float32),
        in_sems=pltpu.SemaphoreType.DMA((N_SLOTS, 2)),
        out_sems=pltpu.SemaphoreType.DMA((N_SLOTS, 2)),
    )


def stream_quant_pack_2d(x2d: jax.Array, noise2d: jax.Array, bits: int = 8,
                         interpret: Optional[bool] = None):
    """(rows, QBLOCK) -> (int8 plane (rows, QBLOCK), fp32 scales (rows, 1)).

    Same math (and bit-identical planes) as ``bitpack.quant_pack_2d``; the
    difference is the explicit two-slot DMA ring moving the tiles.
    """
    rows, qb = x2d.shape
    assert qb == QBLOCK and rows % TILE_ROWS == 0, (x2d.shape,)
    s = 2 ** (bits - 1) - 1
    n_tiles = rows // TILE_ROWS
    q, scales = pl.pallas_call(
        functools.partial(_stream_kernel, n_tiles=n_tiles, s_levels=s),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)],
        out_shape=[
            jax.ShapeDtypeStruct((rows, qb), jnp.int8),
            jax.ShapeDtypeStruct((rows, SCALE_LANES), jnp.float32),
        ],
        interpret=resolve(interpret),
    )(x2d, noise2d)
    return q, scales[:, :1]
