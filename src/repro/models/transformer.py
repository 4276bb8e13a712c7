"""Decoder (and encoder-decoder) stacks for every assigned architecture.

Layer heterogeneity (jamba's 7:1 mamba:attn interleave, llama4's 3:1
chunked:global iRoPE, jamba's every-2nd-layer MoE) is handled with a *period*
abstraction: the layer schedule is tiled from a pattern of length P; params
for each position-in-period are stacked across the ``num_layers / P`` periods
and the stack is driven by ``jax.lax.scan`` — one period traced once, so the
512-way SPMD dry-runs compile in HLO size O(period), not O(num_layers).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import MAMBA, ModelConfig
from repro.models import attention as attn_lib
from repro.models import mamba as mamba_lib
from repro.models import moe as moe_lib
from repro.models.layers import (
    _dense_init,
    cross_entropy_loss,
    embed,
    init_embed,
    init_mlp,
    init_rmsnorm,
    mlp,
    rmsnorm,
    unembed,
)


# ---------------------------------------------------------------------------
# Schedule helpers
# ---------------------------------------------------------------------------
def _lcm(a: int, b: int) -> int:
    return a * b // math.gcd(a, b)


def period_info(cfg: ModelConfig):
    kinds = cfg.layer_kinds()
    base = len(cfg.layer_pattern) if cfg.layer_pattern else 1
    P = _lcm(base, cfg.moe_every if cfg.moe else 1)
    assert cfg.num_layers % P == 0, (cfg.name, cfg.num_layers, P)
    n_periods = cfg.num_layers // P
    pos_kinds = kinds[:P]
    pos_moe = tuple(
        cfg.moe is not None and (j % cfg.moe_every) == cfg.moe_every - 1
        for j in range(P)
    )
    return P, n_periods, pos_kinds, pos_moe


def _attn_cfg(cfg: ModelConfig, kind: str) -> dict:
    return dict(
        num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim,
        kind=kind,
        window=cfg.sliding_window,
        chunk=cfg.attn_chunk,
        qk_norm=cfg.qk_norm,
        # llama4 iRoPE: global (non-chunked) layers are NoPE
        use_rope=not cfg.nope and not (cfg.attn_chunk > 0 and kind == "attn"),
        rope_theta=cfg.rope_theta,
        scale=cfg.attention_multiplier,
    )


def model_dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# The muP-style multipliers are static: at their default of 1.0 they add no
# operation, so a model without them compiles to the same program.
def _scale_embeddings(cfg: ModelConfig, x):
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def _residual(cfg: ModelConfig, h):
    """A sublayer's output as it is added to the residual stream."""
    if cfg.residual_multiplier != 1.0:
        h = h * cfg.residual_multiplier
    return h


def _logits(params, cfg: ModelConfig, x):
    logits = unembed(params["embed"], x)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def _init_block(key, cfg: ModelConfig, kind: str, use_moe: bool, dtype) -> dict:
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    p: dict = {"norm1": init_rmsnorm(d, dtype)}
    if kind == MAMBA:
        p["mamba"] = mamba_lib.init_mamba(ks[0], d, cfg.mamba, dtype)
    else:
        p["attn"] = attn_lib.init_attention(
            ks[0], d, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.qkv_bias, dtype)
    if cfg.cross_attn:
        p["norm_x"] = init_rmsnorm(d, dtype)
        p["xattn"] = attn_lib.init_attention(
            ks[1], d, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, False, dtype)
    if cfg.d_ff > 0:
        p["norm2"] = init_rmsnorm(d, dtype)
        if use_moe:
            p["moe"] = moe_lib.init_moe(
                ks[2], d, cfg.d_ff, cfg.moe.num_experts, cfg.mlp_gated,
                cfg.moe.shared_expert, dtype)
        else:
            p["mlp"] = init_mlp(ks[3], d, cfg.d_ff, cfg.mlp_gated, dtype)
    return p


def init_params(key, cfg: ModelConfig) -> dict:
    dtype = model_dtype(cfg)
    P, n_periods, pos_kinds, pos_moe = period_info(cfg)
    k_embed, k_blocks, k_enc, k_vis = jax.random.split(key, 4)

    params: dict = {
        "embed": init_embed(k_embed, cfg.padded_vocab(), cfg.d_model, dtype,
                            cfg.tie_embeddings),
        "final_norm": init_rmsnorm(cfg.d_model, dtype),
    }

    block_keys = jax.random.split(k_blocks, n_periods * P).reshape(n_periods, P, 2)
    blocks = {}
    for j in range(P):
        stacked = jax.vmap(
            lambda k, j=j: _init_block(k, cfg, pos_kinds[j], pos_moe[j], dtype)
        )(block_keys[:, j])
        blocks[f"pos{j}"] = stacked
    params["blocks"] = blocks

    if cfg.enc_layers:
        de = cfg.enc_d_model or cfg.d_model
        enc_keys = jax.random.split(k_enc, cfg.enc_layers + 1)

        def enc_block(k):
            k1, k2 = jax.random.split(k)
            return {
                "norm1": init_rmsnorm(de, dtype),
                "attn": attn_lib.init_attention(
                    k1, de, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, False, dtype),
                "norm2": init_rmsnorm(de, dtype),
                "mlp": init_mlp(k2, de, cfg.d_ff, cfg.mlp_gated, dtype),
            }

        params["encoder"] = {
            "blocks": jax.vmap(enc_block)(enc_keys[:-1]),
            "final_norm": init_rmsnorm(de, dtype),
        }
    if cfg.vision_tokens:
        params["vision_proj"] = _dense_init(k_vis, (cfg.d_model, cfg.d_model), dtype)
    return params


# ---------------------------------------------------------------------------
# Block application (shared by train / prefill / decode)
# ---------------------------------------------------------------------------
def _apply_block_train(bp, cfg: ModelConfig, kind: str, use_moe: bool, x,
                       enc_out: Optional[jax.Array]):
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm(bp["norm1"], x, cfg.norm_eps)
    if kind == MAMBA:
        h = mamba_lib.mamba_train(bp["mamba"], h, cfg.mamba, cfg.d_model)
    else:
        h = attn_lib.attention_train(bp["attn"], h, cfg_attn=_attn_cfg(cfg, kind))
    x = x + _residual(cfg, h)
    if cfg.cross_attn and enc_out is not None:
        h = rmsnorm(bp["norm_x"], x, cfg.norm_eps)
        h = _cross_attention(bp["xattn"], h, enc_out, cfg)
        x = x + h
    if cfg.d_ff > 0:
        h = rmsnorm(bp["norm2"], x, cfg.norm_eps)
        if use_moe:
            h, a = moe_lib.moe_apply(
                bp["moe"], h, num_experts=cfg.moe.num_experts, top_k=cfg.moe.top_k,
                capacity_factor=cfg.moe.capacity_factor, act=cfg.mlp_act,
                gated=cfg.mlp_gated, shared_expert=cfg.moe.shared_expert)
            aux = aux + a
        else:
            h = mlp(bp["mlp"], h, act=cfg.mlp_act, gated=cfg.mlp_gated)
        x = x + _residual(cfg, h)
    return x, aux


def _cross_attention(params, x, memory, cfg: ModelConfig):
    """Non-causal attention from decoder x (B,Sq,D) to encoder memory (B,Sk,De)."""
    B, Sq, _ = x.shape
    Sk = memory.shape[1]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(B, Sq, H, hd)
    k = (memory @ params["wk"]).reshape(B, Sk, KV, hd)
    v = (memory @ params["wv"]).reshape(B, Sk, KV, hd)
    out = _full_attention_nomask(q, k, v)
    return out.reshape(B, Sq, H * hd) @ params["wo"]


def _full_attention_nomask(q, k, v):
    """Non-causal attention through the tiled flash kernel: the naive
    (B,H,Sq,Sk) score tensor costs 17 GB/chip per seamless encoder layer at
    S=4k — the flash path is numerically identical with O(bq*bk) transients."""
    return attn_lib._flash_attention(q, k, v, "full", 0, 0)


# ---------------------------------------------------------------------------
# Activation-sharding context: the launcher installs a PartitionSpec for the
# residual stream so scan-saved remat residuals are sharded over (data, model)
# instead of replicated over 'model' (cuts saved-activation memory 16x on the
# production mesh). No-op outside a mesh context.
# ---------------------------------------------------------------------------
_ACT_SPEC = None

# Costing-harness switch: unroll the layer-period scan into a python loop so
# HLO cost analysis (which counts while bodies ONCE, ignoring trip counts)
# sees every period.  Only used with 1-2 period variant configs.
UNROLL_SCAN = False


def stack_scan(f, init, xs):
    if not UNROLL_SCAN:
        return jax.lax.scan(f, init, xs)
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]
    carry, ys = init, []
    for i in range(n):
        xi = jax.tree_util.tree_map(lambda a: a[i], xs)
        carry, y = f(carry, xi)
        ys.append(y)
    if ys and ys[0] is None:
        return carry, None
    return carry, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)


def set_activation_sharding(spec) -> None:
    global _ACT_SPEC
    _ACT_SPEC = spec


def _constrain(x):
    if _ACT_SPEC is not None:
        x = jax.lax.with_sharding_constraint(x, _ACT_SPEC)
    return x


def _remat_wrap(fn, remat: str):
    if remat == "none":
        return fn
    if remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# Encoder (seamless)
# ---------------------------------------------------------------------------
def encode(params, cfg: ModelConfig, src_embeds: jax.Array, remat: str = "dots") -> jax.Array:
    enc = params["encoder"]
    acfg = _attn_cfg(cfg, "attn")
    acfg["use_rope"] = True

    def body(x, bp):
        h = rmsnorm(bp["norm1"], x, cfg.norm_eps)
        h = _noncausal_self_attention(bp["attn"], h, acfg)
        x = x + h
        h = rmsnorm(bp["norm2"], x, cfg.norm_eps)
        x = x + mlp(bp["mlp"], h, act=cfg.mlp_act, gated=cfg.mlp_gated)
        return x, None

    x, _ = stack_scan(_remat_wrap(body, remat), src_embeds, enc["blocks"])
    return rmsnorm(enc["final_norm"], x, cfg.norm_eps)


def _noncausal_self_attention(params, x, acfg):
    B, S, _ = x.shape
    H, KV, hd = acfg["num_heads"], acfg["num_kv_heads"], acfg["head_dim"]
    q = (x @ params["wq"]).reshape(B, S, H, hd)
    k = (x @ params["wk"]).reshape(B, S, KV, hd)
    v = (x @ params["wv"]).reshape(B, S, KV, hd)
    from repro.models.layers import apply_rope
    pos = jnp.arange(S)[None, :]
    q = apply_rope(q, pos, acfg["rope_theta"])
    k = apply_rope(k, pos, acfg["rope_theta"])
    out = _full_attention_nomask(q, k, v)
    return out.reshape(B, S, H * hd) @ params["wo"]


# ---------------------------------------------------------------------------
# Forward (train): returns (logits, aux_loss)
# ---------------------------------------------------------------------------
def forward_train(params, cfg: ModelConfig, batch: dict, remat: str = "dots"):
    P, n_periods, pos_kinds, pos_moe = period_info(cfg)
    tokens = batch["tokens"]
    # precomputed embeddings (grad-accum hoists the gather out of its scan —
    # GSPMD's gather partitioning is unsound inside a while body)
    if "inputs_embeds" in batch:
        x = batch["inputs_embeds"]
    else:
        x = embed(params["embed"], tokens)
    x = _scale_embeddings(cfg, x)

    if cfg.vision_tokens and "vision_embeds" in batch:
        vis = batch["vision_embeds"] @ params["vision_proj"]
        nv = vis.shape[1]
        x = jnp.concatenate([vis.astype(x.dtype), x[:, nv:]], axis=1)

    enc_out = None
    if cfg.enc_layers:
        enc_out = encode(params, cfg, batch["src_embeds"].astype(x.dtype), remat)

    def period_body(x, bps):
        aux = jnp.zeros((), jnp.float32)
        for j in range(P):
            x, a = _apply_block_train(bps[f"pos{j}"], cfg, pos_kinds[j], pos_moe[j], x, enc_out)
            aux = aux + a
        # constrain the carry OUTPUT: this is the buffer remat saves per
        # period — sharded (data, model) it is 16x smaller than replicated
        return _constrain(x), aux

    x, auxes = stack_scan(_remat_wrap(period_body, remat), _constrain(x),
                          params["blocks"])
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _logits(params, cfg, x)
    return logits, jnp.sum(auxes)


def loss_fn(params, cfg: ModelConfig, batch: dict, remat: str = "dots"):
    logits, aux = forward_train(params, cfg, batch, remat)
    ce = cross_entropy_loss(logits, batch["targets"], valid_vocab=cfg.vocab_size)
    aux_w = cfg.moe.aux_loss_weight if cfg.moe else 0.0
    return ce + aux_w * aux, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# Serving: prefill + decode with caches
# ---------------------------------------------------------------------------
def cache_specs(cfg: ModelConfig, batch: int, seq_len: int, enc_len: int = 0):
    """ShapeDtypeStruct pytree for the decode cache (+ cross-attn memory)."""
    dtype = model_dtype(cfg)
    P, n_periods, pos_kinds, pos_moe = period_info(cfg)

    def stack(spec):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((n_periods,) + s.shape, s.dtype), spec)

    cache = {}
    for j, kind in enumerate(pos_kinds):
        if kind == MAMBA:
            spec = mamba_lib.mamba_cache_spec(cfg.d_model, cfg.mamba, batch, dtype)
        else:
            spec = attn_lib.cache_spec(_attn_cfg(cfg, kind), batch, seq_len, dtype)
        cache[f"pos{j}"] = stack(spec)
    out = {"layers": cache, "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    if cfg.enc_layers:
        de = cfg.enc_d_model or cfg.d_model
        out["enc_memory"] = jax.ShapeDtypeStruct((batch, enc_len, de), dtype)
    return out


def _apply_block_decode(bp, cfg: ModelConfig, kind: str, use_moe: bool, x, caches,
                        layer, pos, enc_memory):
    """One block of one decode step. ``caches`` holds this block position's
    caches of every period stacked; the block reads and writes its own,
    ``layer``, in place. Returns (x, caches)."""
    h = rmsnorm(bp["norm1"], x, cfg.norm_eps)
    if kind == MAMBA:
        h, caches = mamba_lib.mamba_decode(
            bp["mamba"], h, caches, layer, cfg.mamba, cfg.d_model)
    else:
        h, caches = attn_lib.attention_decode(
            bp["attn"], h, caches, pos, layer, cfg_attn=_attn_cfg(cfg, kind))
    x = x + _residual(cfg, h)
    if cfg.cross_attn and enc_memory is not None:
        h = rmsnorm(bp["norm_x"], x, cfg.norm_eps)
        x = x + _cross_attention(bp["xattn"], h, enc_memory, cfg)
    if cfg.d_ff > 0:
        h = rmsnorm(bp["norm2"], x, cfg.norm_eps)
        if use_moe:
            h, _ = moe_lib.moe_ffn(
                bp["moe"], h, num_experts=cfg.moe.num_experts, top_k=cfg.moe.top_k,
                capacity_factor=cfg.moe.capacity_factor, act=cfg.mlp_act,
                gated=cfg.mlp_gated, shared_expert=cfg.moe.shared_expert,
                no_drop=True)
        else:
            h = mlp(bp["mlp"], h, act=cfg.mlp_act, gated=cfg.mlp_gated)
        x = x + _residual(cfg, h)
    return x, caches


def decode_step(params, cfg: ModelConfig, token: jax.Array, cache: dict):
    """token (B, 1) int32; cache from cache_specs/prefill. Returns (logits, cache).

    The stacked layer caches ride in the layer scan's carry, so each layer
    writes its token into them in place rather than the scan slicing every
    layer's cache out and stacking a new one."""
    P, n_periods, pos_kinds, pos_moe = period_info(cfg)
    pos = cache["pos"]
    x = _scale_embeddings(cfg, embed(params["embed"], token))
    enc_memory = cache.get("enc_memory")

    def period_body(carry, scanned):
        x, caches = carry
        bps, layer = scanned
        caches = dict(caches)
        for j in range(P):
            x, caches[f"pos{j}"] = _apply_block_decode(
                bps[f"pos{j}"], cfg, pos_kinds[j], pos_moe[j], x, caches[f"pos{j}"],
                layer, pos, enc_memory)
        return (x, caches), None

    (x, new_layer_caches), _ = stack_scan(
        period_body, (x, cache["layers"]),
        (params["blocks"], jnp.arange(n_periods, dtype=jnp.int32)))
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _logits(params, cfg, x)
    new_cache = dict(cache)
    new_cache["layers"] = new_layer_caches
    new_cache["pos"] = pos + 1
    return logits, new_cache


def _ring_from_prefill(kv: dict, cfg_attn: dict, S: int, cache_len: int):
    """Convert full prefill K/V (B,S,KV,hd) into the sequence-major decode
    cache (Sc,B,KV,hd).

    Windowed kinds get a ring of the last `Sc` live positions placed so that
    slot == pos % Sc; the global kind gets a slot==pos cache padded out to
    ``cache_len`` capacity so subsequent decode steps append without wrapping.
    """
    kind = cfg_attn["kind"]
    if kind == "attn_swa":
        Sc = min(cache_len, cfg_attn["window"])
    elif kind == "attn_chunk":
        Sc = min(cache_len, cfg_attn["chunk"])
    else:
        Sc = max(cache_len, S)

    def ring(a):
        a = a.swapaxes(0, 1)  # (S, B, KV, hd)
        if S < Sc:
            return jnp.pad(a, ((0, Sc - S), (0, 0), (0, 0), (0, 0)))  # slot == pos
        tail = a[S - Sc:]
        # element j holds pos S-Sc+j whose slot is (S-Sc+j) % Sc == (j + S) % Sc
        return jnp.roll(tail, shift=S % Sc, axis=0)

    return {"k": ring(kv["k"]), "v": ring(kv["v"])}


def prefill(params, cfg: ModelConfig, batch: dict, remat: str = "dots",
            cache_len: int = 0):
    """Full-sequence forward producing (last-position logits, decode cache).

    The cache matches ``cache_specs(cfg, B, S)`` exactly: attention layers get
    their K/V (ring-rolled to window size for SWA/chunked kinds), SSD layers
    get {ssm state, conv tail}; enc-dec additionally stores the encoder memory.
    """
    P, n_periods, pos_kinds, pos_moe = period_info(cfg)
    tokens = batch["tokens"]
    B, S = tokens.shape
    cache_len = max(cache_len, S + 1)
    x = _scale_embeddings(cfg, embed(params["embed"], tokens))
    if cfg.vision_tokens and "vision_embeds" in batch:
        vis = batch["vision_embeds"] @ params["vision_proj"]
        nv = vis.shape[1]
        x = jnp.concatenate([vis.astype(x.dtype), x[:, nv:]], axis=1)
    enc_out = None
    if cfg.enc_layers:
        enc_out = encode(params, cfg, batch["src_embeds"].astype(x.dtype), remat)

    def period_body(x, bps):
        caches = {}
        for j in range(P):
            kind, use_moe = pos_kinds[j], pos_moe[j]
            bp = bps[f"pos{j}"]
            h = rmsnorm(bp["norm1"], x, cfg.norm_eps)
            if kind == MAMBA:
                h, cache_j = mamba_lib.mamba_forward(
                    bp["mamba"], h, cfg.mamba, cfg.d_model, return_cache=True)
            else:
                acfg = _attn_cfg(cfg, kind)
                h, kv = attn_lib.attention_prefill(bp["attn"], h, cfg_attn=acfg)
                cache_j = _ring_from_prefill(kv, acfg, S, cache_len)
            caches[f"pos{j}"] = cache_j
            x = x + _residual(cfg, h)
            if cfg.cross_attn and enc_out is not None:
                hx = rmsnorm(bp["norm_x"], x, cfg.norm_eps)
                x = x + _cross_attention(bp["xattn"], hx, enc_out, cfg)
            if cfg.d_ff > 0:
                h2 = rmsnorm(bp["norm2"], x, cfg.norm_eps)
                if use_moe:
                    h2, _ = moe_lib.moe_apply(
                        bp["moe"], h2, num_experts=cfg.moe.num_experts,
                        top_k=cfg.moe.top_k, capacity_factor=cfg.moe.capacity_factor,
                        act=cfg.mlp_act, gated=cfg.mlp_gated,
                        shared_expert=cfg.moe.shared_expert)
                else:
                    h2 = mlp(bp["mlp"], h2, act=cfg.mlp_act, gated=cfg.mlp_gated)
                x = x + _residual(cfg, h2)
        return _constrain(x), caches

    x, layer_caches = stack_scan(_remat_wrap(period_body, remat), _constrain(x),
                                 params["blocks"])
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _logits(params, cfg, x[:, -1:])
    cache = {"layers": layer_caches, "pos": jnp.asarray(S, jnp.int32)}
    if cfg.enc_layers:
        cache["enc_memory"] = enc_out
    return logits, cache
