"""Jit'd public wrappers around the Pallas kernels.

These handle shape plumbing (flat -> tiled 2D with padding), compute the
cheap global statistics the kernels consume (per-output thresholds, RIA
row/col sums, symwanda normalizers), and expose drop-in backends:

  * ``quantize_dequantize``  — compressor backend (core/compressors.qsgd)
  * ``prune_nm``             — N:M backend for core/symwanda.mask_nm
  * ``prune_scored``         — fused score+mask backend for core/symwanda.prune
  * ``ssm_state_step``       — Mamba-2 decode state step, in place
                               (models/mamba.mamba_decode)

``interpret=None`` leaves the choice to kernels/backend.py: compiled on a
TPU, interpreted on the CPU.  Passing True or False overrides it.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import bitpack as _bp
from repro.kernels import nm_prune as _nm
from repro.kernels import quant8 as _q8
from repro.kernels import ssm_step as _ss
from repro.kernels import wanda_score as _ws
from repro.kernels import ref as _ref


# ---------------------------------------------------------------------------
# bitpack (repro.comm wire formats)
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("interpret",))
def pack_bits(mask: jax.Array, interpret: Optional[bool] = None) -> jax.Array:
    """Flat {0,1} mask (d,) -> uint32 word stream (ceil(d/32),).

    Bit layout: with W = ceil(d/32), bit j of word w is mask[j*W + w] — the
    stride-W order lets the kernel reduce along the 32 sublanes with lanes
    kept 128-aligned.  ``unpack_bits`` inverts it exactly.
    """
    d = mask.shape[0]
    w = -(-d // _bp.PACK_BITS)
    wp = -(-w // _bp.PACK_LANES) * _bp.PACK_LANES
    m2d = (jnp.zeros((_bp.PACK_BITS * w,), jnp.uint32).at[:d]
           .set(mask.astype(jnp.uint32)).reshape(_bp.PACK_BITS, w))
    m2d = jnp.zeros((_bp.PACK_BITS, wp), jnp.uint32).at[:, :w].set(m2d)
    return _bp.pack_mask_2d(m2d, interpret=interpret)[0, :w]


@partial(jax.jit, static_argnames=("d", "interpret"))
def unpack_bits(words: jax.Array, d: int, interpret: Optional[bool] = None) -> jax.Array:
    """Inverse of pack_bits: (ceil(d/32),) uint32 -> (d,) {0,1} uint32."""
    w = words.shape[0]
    assert w == -(-d // _bp.PACK_BITS), (w, d)
    wp = -(-w // _bp.PACK_LANES) * _bp.PACK_LANES
    wpad = jnp.zeros((1, wp), jnp.uint32).at[0, :w].set(words)
    bits = _bp.unpack_mask_2d(wpad, interpret=interpret)
    return bits[:, :w].reshape(-1)[:d]


def _quant_tiles(x: jax.Array, key: jax.Array):
    """Shared shape plumbing of every quantize entry point: pad the flat
    tensor to whole (TILE_ROWS, QBLOCK) tiles and draw the stochastic-round
    noise.  ONE definition on purpose — quantize_pack, stream_quantize_pack
    and quantize_dequantize are bit-identical only while they pad and draw
    noise identically."""
    flat = x.reshape(-1)
    d = flat.shape[0]
    qb, tr = _q8.QBLOCK, _q8.TILE_ROWS
    rows = -(-d // qb)
    rows_pad = -(-rows // tr) * tr
    padded = jnp.zeros((rows_pad * qb,), x.dtype).at[:d].set(flat).reshape(rows_pad, qb)
    noise = jax.random.uniform(key, padded.shape, jnp.float32)
    return padded, noise, d


@partial(jax.jit, static_argnames=("bits", "interpret"))
def quantize_pack(x: jax.Array, key: jax.Array, bits: int = 8,
                  interpret: Optional[bool] = None):
    """Flat/any-shape tensor -> (int8 plane (rows, QBLOCK), scales (rows, 1)).

    Shape plumbing (padding, noise draw) matches quantize_dequantize exactly,
    so ``q * scales`` reproduces its dequantized output bit-for-bit — the
    codec's decode of the wire planes equals the on-chip compressor carrier.
    """
    padded, noise, _ = _quant_tiles(x, key)
    return _bp.quant_pack_2d(padded, noise, bits=bits, interpret=interpret)


@partial(jax.jit, static_argnames=("d", "interpret"))
def unpack_dequantize(q: jax.Array, scales: jax.Array, d: int,
                      interpret: Optional[bool] = None) -> jax.Array:
    """Inverse of quantize_pack: wire planes -> flat (d,) float32 tensor."""
    out = _bp.unpack_dequant_2d(q, scales, interpret=interpret)
    return out.reshape(-1)[:d]


@partial(jax.jit, static_argnames=("bits", "interpret"))
def stream_quantize_pack(x: jax.Array, key: jax.Array, bits: int = 8,
                         interpret: Optional[bool] = None):
    """quantize_pack via the double-buffered streaming DMA ring
    (kernels/stream.py).  Identical shape plumbing and noise draw, so the
    wire planes are bit-identical to ``quantize_pack``'s."""
    from repro.kernels import stream as _st

    padded, noise, _ = _quant_tiles(x, key)
    return _st.stream_quant_pack_2d(padded, noise, bits=bits, interpret=interpret)


def nibble_pack(q: jax.Array) -> jax.Array:
    """int8 plane with values in [-8, 7] -> two-per-byte uint8 (transport
    packing for 4-bit quantizers; pure jnp — runs at round boundaries)."""
    u = (q.reshape(-1).astype(jnp.int32) + 8).astype(jnp.uint8)
    if u.shape[0] % 2:
        u = jnp.concatenate([u, jnp.zeros((1,), jnp.uint8)])
    return (u[0::2] | (u[1::2] << 4)).astype(jnp.uint8)


def nibble_unpack(packed: jax.Array, n: int) -> jax.Array:
    """Inverse of nibble_pack -> int8 (n,) values in [-8, 7]."""
    lo = (packed & 0xF).astype(jnp.int32) - 8
    hi = ((packed >> 4) & 0xF).astype(jnp.int32) - 8
    return jnp.stack([lo, hi], axis=1).reshape(-1)[:n].astype(jnp.int8)


# ---------------------------------------------------------------------------
# quant8
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("bits", "interpret"))
def quantize_dequantize(x: jax.Array, key: jax.Array, bits: int = 8,
                        interpret: Optional[bool] = None) -> jax.Array:
    """Blockwise absmax quantize-dequantize of an arbitrary-shape tensor."""
    padded, noise, d = _quant_tiles(x, key)
    out = _q8.quant_dequant_2d(padded, noise, bits=bits, interpret=interpret)
    return out.reshape(-1)[:d].reshape(x.shape)


# ---------------------------------------------------------------------------
# N:M prune
# ---------------------------------------------------------------------------
def _pad2d(a, tr, tc):
    r, c = a.shape
    rp, cp = -(-r // tr) * tr, -(-c // tc) * tc
    if (rp, cp) == (r, c):
        return a, r, c
    return jnp.zeros((rp, cp), a.dtype).at[:r, :c].set(a), r, c


@partial(jax.jit, static_argnames=("n", "m", "interpret"))
def prune_nm(w: jax.Array, scores: jax.Array, n: int = 2, m: int = 4,
             interpret: Optional[bool] = None):
    """(d_in, d_out) N:M prune by score; returns (pruned, mask)."""
    wp, r, c = _pad2d(w, _nm.TILE_R, _nm.TILE_C)
    # padded score rows must never win: fill with -inf
    sp = jnp.full(wp.shape, -jnp.inf, jnp.float32).at[:r, :c].set(
        scores.astype(jnp.float32))
    out, mask = _nm.nm_prune_2d(wp, sp, n=n, m=m, interpret=interpret)
    return out[:r, :c], mask[:r, :c]


# ---------------------------------------------------------------------------
# fused wanda/ria/symwanda prune
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("mode", "sparsity", "interpret"))
def prune_scored(w: jax.Array, X: jax.Array, mode: str = "wanda",
                 sparsity: float = 0.5, alpha: float = 0.5, beta: float = 0.5,
                 interpret: Optional[bool] = None):
    """Fused score+mask prune of w (d_in, d_out) with calibration X (T, d_in).

    Per-output thresholds come from a top-k over the (recomputed-on-the-fly)
    score columns; the kernel then re-derives scores tile-local and masks.
    Returns (pruned, mask)."""
    d_in, d_out = w.shape
    xnorm = jnp.sqrt(jnp.sum(X.astype(jnp.float32) ** 2, axis=0))
    kw = dict(mode=mode, alpha=alpha, beta=beta)
    rowsum = colsum = ynorm = None
    mu_in = mu_out = 1.0
    if mode == "ria":
        aw = jnp.abs(w.astype(jnp.float32))
        rowsum = jnp.sum(aw, axis=1)
        colsum = jnp.sum(aw, axis=0)
        scores = _ref.wanda_scores_ref(w, xnorm, mode, alpha)
    elif mode == "symwanda":
        Y = X @ w
        ynorm = jnp.sqrt(jnp.sum(Y.astype(jnp.float32) ** 2, axis=0))
        aw = jnp.abs(w.astype(jnp.float32))
        mu_in = jnp.mean(aw * xnorm[:, None])
        mu_out = jnp.mean(aw * ynorm[None, :])
        scores = _ref.wanda_scores_ref(w, xnorm, mode, alpha, beta, ynorm, mu_in, mu_out)
        rowsum, colsum = mu_in, mu_out  # packed as scalars for the kernel
    else:
        scores = _ref.wanda_scores_ref(w, xnorm, "wanda")
    k = max(1, int(round((1 - sparsity) * d_in)))
    tau = jax.lax.top_k(scores.T, k)[0][:, -1]  # per output column

    wp, r, c = _pad2d(w, _ws.TILE_R, _ws.TILE_C)
    xn_p = jnp.zeros((wp.shape[0],), jnp.float32).at[:r].set(xnorm)
    tau_p = jnp.full((wp.shape[1],), jnp.inf, jnp.float32).at[:c].set(tau)
    if mode == "ria":
        rs_p = jnp.ones((wp.shape[0],), jnp.float32).at[:r].set(rowsum)
        cs_p = jnp.ones((wp.shape[1],), jnp.float32).at[:c].set(colsum)
        out, mask = _ws.wanda_prune_2d(wp, xn_p, tau_p, mode=mode, alpha=alpha,
                                       rowsum=rs_p, colsum=cs_p, interpret=interpret)
    elif mode == "symwanda":
        yn_p = jnp.zeros((wp.shape[1],), jnp.float32).at[:c].set(ynorm)
        out, mask = _ws.wanda_prune_2d(wp, xn_p, tau_p, mode=mode, beta=beta,
                                       rowsum=mu_in, colsum=mu_out, ynorm=yn_p,
                                       interpret=interpret)
    else:
        out, mask = _ws.wanda_prune_2d(wp, xn_p, tau_p, mode="wanda",
                                       interpret=interpret)
    return out[:r, :c], mask[:r, :c]


# ---------------------------------------------------------------------------
# Mamba-2 decode state step
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("interpret",))
def ssm_state_step(state: jax.Array, layer: jax.Array, da: jax.Array,
                   dt: jax.Array, x: jax.Array, b: jax.Array, c: jax.Array,
                   interpret: Optional[bool] = None):
    """One decode step of layer ``layer`` of the stacked f32 SSM state
    (P, B, H, hd, N), written where it lies: da, dt (B, H); x (B, H, hd);
    b, c (B, G, N).  Returns (the stack, aliased to ``state``, and the
    readout y (B, H, hd) without the ``D`` skip)."""
    return _ss.state_step(state, layer, da, dt[..., None] * x, b, c,
                          interpret=interpret)
