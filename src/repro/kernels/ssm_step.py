"""Pallas TPU kernel: one Mamba-2 decode step of one layer's SSM state, in place.

The decode carries the recurrent state of every period's Mamba layer at one
position of the layer pattern stacked, f32 ``(n_periods, B, H, hd, N)``.  A
step of layer ``layer`` is

    h' = da * h + (dt * x) outer B        y = sum_n h' * C

per slot and head, with ``B`` and ``C`` shared by the heads of a group.  In
``jax.numpy`` XLA writes ``h'`` to a temporary while it reduces the readout,
then copies the temporary into the stack: the state crosses HBM four times.
Here each grid step reads one block of heads of one slot once, writes ``h'``
back where it lies (the stack is aliased from input to output, the layer
comes in as a scalar-prefetch argument of the block index) and reduces ``y``
from the tile while it is in VMEM: one read and one write.

Blocks hold whole ``(hd, N)`` tiles of as many heads of one group as fit
``BLOCK_BYTES``; a head's group is picked by the ``index_map`` of ``B`` and
``C``.  ``hd`` must be a multiple of 8 and ``N`` of 128 (the f32 tile).
The oracle is the model's own ``jax.numpy`` step,
``repro.models.mamba._state_step``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve

# one slot's 64 heads of (64, 128) at granite-4.0-h's widths; in and out
# blocks, double-buffered, take 4x this of VMEM
BLOCK_BYTES = 2 * 1024 * 1024


def heads_per_block(heads_per_group: int, hd: int, n: int) -> int:
    """The most heads of one group whose f32 state fits ``BLOCK_BYTES``,
    dividing the group's heads."""
    per_head = hd * n * 4
    return max(d for d in range(1, heads_per_group + 1)
               if heads_per_group % d == 0
               and (d * per_head <= BLOCK_BYTES or d == 1))


def _step_kernel(layer_ref, h_ref, da_ref, xs_ref, b_ref, c_ref,
                 out_ref, y_ref):
    del layer_ref                        # consumed by the index maps
    da = jnp.transpose(da_ref[0, 0])[:, :, None]          # (hb, 1, 1)
    h = (h_ref[0, 0] * da
         + xs_ref[0, 0][:, :, None] * b_ref[0, 0][None])   # (hb, hd, N)
    out_ref[0, 0] = h
    y_ref[0, 0] = jnp.sum(h * c_ref[0, 0][None], axis=-1)


def state_step(state: jax.Array, layer: jax.Array, da: jax.Array,
               xs: jax.Array, b: jax.Array, c: jax.Array,
               interpret: Optional[bool] = None):
    """Layer ``layer`` of ``state`` (P, B, H, hd, N) f32 steps in place.

    da (B, H) decay, xs (B, H, hd) = dt * x, b and c (B, G, N).  Returns the
    stack with that layer's slots replaced (aliased to ``state``) and the
    readout y (B, H, hd) f32, without the ``D`` skip."""
    _, slots, heads, hd, n = state.shape
    g = b.shape[1]
    hb = heads_per_block(heads // g, hd, n)
    blocks = heads // hb
    per_group = (heads // g) // hb
    da4 = da.reshape(slots, blocks, 1, hb)
    xs4 = xs.reshape(slots, blocks, hb, hd)
    b4 = b.reshape(slots, g, 1, n)
    c4 = c.reshape(slots, g, 1, n)

    def state_map(i, j, layer_ref):
        return layer_ref[0], i, j, 0, 0

    def head_map(i, j, layer_ref):
        return i, j, 0, 0

    def group_map(i, j, layer_ref):
        return i, j // per_group, 0, 0

    state_spec = pl.BlockSpec((1, 1, hb, hd, n), state_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(slots, blocks),
        in_specs=[state_spec,
                  pl.BlockSpec((1, 1, 1, hb), head_map),
                  pl.BlockSpec((1, 1, hb, hd), head_map),
                  pl.BlockSpec((1, 1, 1, n), group_map),
                  pl.BlockSpec((1, 1, 1, n), group_map)],
        out_specs=[state_spec, pl.BlockSpec((1, 1, hb, hd), head_map)])
    new, y = pl.pallas_call(
        _step_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((slots, blocks, hb, hd), jnp.float32)],
        input_output_aliases={1: 0},     # operand 0 is the layer index
        name="ssm_state_step",
        interpret=resolve(interpret),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), state, da4, xs4, b4, c4)
    return new, y.reshape(slots, heads, hd)
