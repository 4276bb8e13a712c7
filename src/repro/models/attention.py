"""GQA attention with global / sliding-window / chunked-local variants.

Two entry points:
  * ``attention``         — full-sequence causal attention, blockwise
    (flash-style) over KV so S=32k never materializes an S x S score matrix;
    also returns the sequence's K/V, which prefill keeps as its cache.
  * ``attention_decode``  — one query token against a cache (full, ring-buffer
    for SWA/chunked, per the layer kind).

Shapes: x (B, S, D); heads H query / KV kv-heads (GQA groups G = H/KV).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import _dense_init, apply_rope, l2norm

NEG_INF = -1e30


def init_attention(key, d_model: int, num_heads: int, num_kv_heads: int, head_dim: int,
                   bias: bool, dtype) -> dict:
    ks = jax.random.split(key, 4)
    q_dim, kv_dim = num_heads * head_dim, num_kv_heads * head_dim
    p = {
        "wq": _dense_init(ks[0], (d_model, q_dim), dtype),
        "wk": _dense_init(ks[1], (d_model, kv_dim), dtype),
        "wv": _dense_init(ks[2], (d_model, kv_dim), dtype),
        "wo": _dense_init(ks[3], (q_dim, d_model), dtype),
    }
    if bias:
        p["bq"] = jnp.zeros((q_dim,), dtype)
        p["bk"] = jnp.zeros((kv_dim,), dtype)
        p["bv"] = jnp.zeros((kv_dim,), dtype)
    return p


def _project_qkv(params, x, num_heads, num_kv_heads, head_dim, qk_norm, use_rope,
                 positions, rope_theta):
    B, S, _ = x.shape
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if "bq" in params:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(B, S, num_heads, head_dim)
    k = k.reshape(B, S, num_kv_heads, head_dim)
    v = v.reshape(B, S, num_kv_heads, head_dim)
    if qk_norm:
        q, k = l2norm(q), l2norm(k)
    if use_rope:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    return q, k, v


def _block_mask(q_idx, k_idx, kind: str, window: int, chunk: int):
    """(Sq, Sk) additive mask for one (q-block, k-block) pair of indices."""
    if kind == "full":  # non-causal (encoder / cross-attention)
        return jnp.zeros((q_idx.shape[0], k_idx.shape[0]), jnp.float32)
    causal = q_idx[:, None] >= k_idx[None, :]
    ok = causal
    if kind == "attn_swa":
        ok = ok & (q_idx[:, None] - k_idx[None, :] < window)
    elif kind == "attn_chunk":
        ok = ok & ((q_idx[:, None] // chunk) == (k_idx[None, :] // chunk))
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


# Tile sizes: (BLOCK_Q x BLOCK_K) transient score tiles.
BLOCK_Q = 512
BLOCK_K = 1024


def _flash_attention(q, k, v, kind: str, window: int, chunk: int,
                     q_offset: int = 0, block_q: Optional[int] = None,
                     block_k: Optional[int] = None,
                     scale: Optional[float] = None):
    """2D-tiled (flash-style) softmax attention. q (B,Sq,H,hd); k,v (B,Sk,KV,hd).
    ``scale`` multiplies the scores; None is 1/sqrt(hd).

    Outer scan over query tiles, inner scan over KV tiles keeping a running
    (max, denom, accum) per query.  The inner body is wrapped in
    ``jax.checkpoint`` so reverse-mode AD recomputes the (Bq x Bk) score tile
    instead of stashing one per iteration — transient memory is
    O(block_q * block_k) and saved residuals are O(S) per layer.
    """
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    bq = min(block_q or BLOCK_Q, Sq)
    bk = min(block_k or BLOCK_K, Sk)
    nq = -(-Sq // bq)
    nk = -(-Sk // bk)
    if nq * bq != Sq:
        qpad = nq * bq - Sq
        q = jnp.pad(q, ((0, 0), (0, qpad), (0, 0), (0, 0)))
    if nk * bk != Sk:
        kpad = nk * bk - Sk
        k = jnp.pad(k, ((0, 0), (0, kpad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, kpad), (0, 0), (0, 0)))

    qf = (q.astype(jnp.float32) * scale).reshape(B, nq, bq, KV, G, hd)
    kb = k.reshape(B, nk, bk, KV, hd)
    vb = v.reshape(B, nk, bk, KV, hd)

    @jax.checkpoint
    def kv_body(carry, blk):
        q_tile, qi = carry[3], carry[4]
        m_prev, l_prev, acc = carry[0], carry[1], carry[2]
        k_blk, v_blk, ki = blk
        q_idx = q_offset + qi * bq + jnp.arange(bq)
        k_idx = ki * bk + jnp.arange(bk)
        s = jnp.einsum("bqkgh,bnkh->bqkgn", q_tile, k_blk.astype(jnp.float32))
        mask = _block_mask(q_idx, k_idx, kind, window, chunk)   # (bq, bk)
        pad_mask = jnp.where(k_idx < Sk, 0.0, NEG_INF)
        s = s + (mask + pad_mask[None, :])[None, :, None, None, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bqkgn,bnkh->bqkgh", p, v_blk.astype(jnp.float32))
        return (m_new, l_new, acc, q_tile, qi), None

    kbs = kb.swapaxes(0, 1)  # (nk, B, bk, KV, hd)
    vbs = vb.swapaxes(0, 1)

    def q_body(_, q_blk):
        q_tile, qi = q_blk
        m0 = jnp.full((B, bq, KV, G), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, bq, KV, G), jnp.float32)
        a0 = jnp.zeros((B, bq, KV, G, hd), jnp.float32)
        (m, l, acc, _, _), _ = jax.lax.scan(
            kv_body, (m0, l0, a0, q_tile, qi), (kbs, vbs, jnp.arange(nk)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out

    _, out = jax.lax.scan(q_body, None,
                          (qf.swapaxes(0, 1), jnp.arange(nq)))
    # out: (nq, B, bq, KV, G, hd) -> (B, Sq, H, hd)
    out = out.swapaxes(0, 1).reshape(B, nq * bq, H, hd)[:, :Sq]
    return out.astype(q.dtype)


def attention(params, x, *, cfg_attn: dict):
    """Causal attention over the whole sequence x (B,S,D).  cfg_attn keys:
    num_heads num_kv_heads head_dim kind window chunk qk_norm use_rope
    rope_theta scale (None: 1/sqrt(head_dim)).  Returns (output, {k, v}),
    the full (B,S,KV,hd) K/V that prefill turns into the decode cache."""
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]
    q, k, v = _project_qkv(params, x, cfg_attn["num_heads"], cfg_attn["num_kv_heads"],
                           cfg_attn["head_dim"], cfg_attn["qk_norm"], cfg_attn["use_rope"],
                           positions, cfg_attn["rope_theta"])
    out = _flash_attention(q, k, v, cfg_attn["kind"], cfg_attn["window"], cfg_attn["chunk"],
                           scale=cfg_attn["scale"])
    out = out.reshape(B, S, -1) @ params["wo"]
    return out, {"k": k, "v": v}


def cache_spec(cfg_attn: dict, batch: int, seq_len: int, dtype):
    """Decode-cache shapes for one attention layer, sequence-major:
    (S, B, KV, hd), so that writing one position touches whole (B, hd)
    tiles and needs no relayout of the rest.

    SWA / chunked layers bound the live context, so the cache is the window
    (this is exactly why those archs qualify for long_500k)."""
    kind = cfg_attn["kind"]
    if kind == "attn_swa":
        S = min(seq_len, cfg_attn["window"])
    elif kind == "attn_chunk":
        S = min(seq_len, cfg_attn["chunk"])
    else:
        S = seq_len
    kv, hd = cfg_attn["num_kv_heads"], cfg_attn["head_dim"]
    return {
        "k": jax.ShapeDtypeStruct((S, batch, kv, hd), dtype),
        "v": jax.ShapeDtypeStruct((S, batch, kv, hd), dtype),
    }


def attention_decode(params, x, cache: dict, pos: jax.Array, layer, *,
                     cfg_attn: dict):
    """One-token decode of one layer. x (B,1,D); cache{k,v} the caches of
    every layer stacked, (L,Sc,B,KV,hd); layer () int32 — which of them;
    pos () int32 — number of tokens already in context.

    The token's K/V is written into the stack in place at (layer, slot),
    a ring slot for windowed layers, and the layer's K/V is read where it
    is stored; returns (out, updated stack)."""
    B = x.shape[0]
    H, KV, hd = cfg_attn["num_heads"], cfg_attn["num_kv_heads"], cfg_attn["head_dim"]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(params, x, H, KV, hd, cfg_attn["qk_norm"],
                                   cfg_attn["use_rope"], positions, cfg_attn["rope_theta"])
    Sc = cache["k"].shape[1]
    slot = jnp.mod(pos, Sc)  # ring for windowed layers; == pos when Sc==seq_len

    def write(stack, new):  # new (B,1,KV,hd) -> (1,1,B,KV,hd) at (layer, slot)
        stack = jax.lax.dynamic_update_slice(stack, new.swapaxes(0, 1)[None],
                                             (layer, slot, 0, 0, 0))
        return stack, jax.lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)

    k_all, k = write(cache["k"], k_new)
    v_all, v = write(cache["v"], v_new)

    # live-slot mask: slot index valid if it holds one of the last `live` tokens
    kind = cfg_attn["kind"]
    idx = jnp.arange(Sc)
    age_by_slot = jnp.mod(slot - idx, Sc)  # 0 = newest
    written = idx <= jnp.minimum(pos, Sc - 1)  # slots ever written
    if kind == "attn_swa":
        live = age_by_slot < cfg_attn["window"]
    elif kind == "attn_chunk":
        # tokens in the current chunk only
        pos_of_slot = pos - age_by_slot
        live = (pos_of_slot // cfg_attn["chunk"]) == (pos // cfg_attn["chunk"])
    else:
        live = jnp.ones((Sc,), bool)
    valid = (written & live).astype(jnp.float32)
    bias = jnp.where(valid > 0, 0.0, NEG_INF)

    scale = cfg_attn["scale"]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    qf = (q.astype(jnp.float32) * scale).reshape(B, 1, KV, H // KV, hd)
    s = jnp.einsum("bqkgh,nbkh->bqkgn", qf, k.astype(jnp.float32))
    s = s + bias[None, None, None, None, :]
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqkgn,nbkh->bqkgh", p, v.astype(jnp.float32))
    out = out.reshape(B, 1, H * hd).astype(x.dtype) @ params["wo"]
    return out, {"k": k_all, "v": v_all}
