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

import jax
import jax.numpy as jnp

from repro.configs.base import MAMBA, ModelConfig
from repro.models import attention as attn_lib
from repro.models import mamba as mamba_lib
from repro.models import moe as moe_lib
from repro.models.layers import (
    _dense_init,
    cross_entropy_loss,
    apply_rope,
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
# The block, which train, prefill and decode all apply
# ---------------------------------------------------------------------------
def _apply_block(bp, cfg: ModelConfig, kind: str, use_moe: bool, x, memory,
                 mix, moe, state=None):
    """One block: norm1, the mixer, residual, cross-attention to ``memory``
    (with one), norm2, the MLP or MoE, residual.

    What differs between the uses comes in as calls:
    ``mix(bp, kind, h, state)`` runs the block's attention or Mamba layer
    and returns (h, the layer's cache), and ``moe(params, h, **kw)`` returns
    (h, router aux loss).  Returns (x, cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm(bp["norm1"], x, cfg.norm_eps)
    h, state = mix(bp, kind, h, state)
    x = x + _residual(cfg, h)
    if cfg.cross_attn and memory is not None:
        h = rmsnorm(bp["norm_x"], x, cfg.norm_eps)
        x = x + _unmasked_attention(bp["xattn"], h, memory, cfg)
    if cfg.d_ff > 0:
        h = rmsnorm(bp["norm2"], x, cfg.norm_eps)
        if use_moe:
            h, a = moe(
                bp["moe"], h, num_experts=cfg.moe.num_experts, top_k=cfg.moe.top_k,
                capacity_factor=cfg.moe.capacity_factor, act=cfg.mlp_act,
                gated=cfg.mlp_gated, shared_expert=cfg.moe.shared_expert)
            aux = aux + a
        else:
            h = mlp(bp["mlp"], h, act=cfg.mlp_act, gated=cfg.mlp_gated)
        x = x + _residual(cfg, h)
    return x, state, aux


def _unmasked_attention(params, x, memory, cfg: ModelConfig, rope: bool = False):
    """Non-causal attention from x (B,Sq,D) to memory (B,Sk,De): the
    decoder's cross-attention, or with ``memory`` x and ``rope`` the
    encoder's self-attention.  It runs through the tiled flash attention: the
    naive (B,H,Sq,Sk) score tensor costs 17 GB/chip per seamless encoder
    layer at S=4k."""
    B, Sq, _ = x.shape
    Sk = memory.shape[1]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(B, Sq, H, hd)
    k = (memory @ params["wk"]).reshape(B, Sk, KV, hd)
    v = (memory @ params["wv"]).reshape(B, Sk, KV, hd)
    if rope:
        pos = jnp.arange(Sq)[None, :]
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    out = attn_lib._flash_attention(q, k, v, "full", 0, 0)
    return out.reshape(B, Sq, H * hd) @ params["wo"]


# ---------------------------------------------------------------------------
# Activation-sharding context: the launcher installs a PartitionSpec for the
# residual stream so scan-saved remat residuals are sharded over (data, model)
# instead of replicated over 'model' (cuts saved-activation memory 16x on the
# production mesh). No-op outside a mesh context.
# ---------------------------------------------------------------------------
_ACT_SPEC = None


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

    def body(x, bp):
        h = rmsnorm(bp["norm1"], x, cfg.norm_eps)
        h = _unmasked_attention(bp["attn"], h, h, cfg, rope=True)
        x = x + h
        h = rmsnorm(bp["norm2"], x, cfg.norm_eps)
        x = x + mlp(bp["mlp"], h, act=cfg.mlp_act, gated=cfg.mlp_gated)
        return x, None

    x, _ = jax.lax.scan(_remat_wrap(body, remat), src_embeds, enc["blocks"])
    return rmsnorm(enc["final_norm"], x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# Whole sequences: train and prefill
# ---------------------------------------------------------------------------
def _embed_inputs(params, cfg: ModelConfig, batch: dict, remat: str):
    """The residual stream's input (B,S,D) and the encoder's memory (None
    without an encoder): the token embedding, or ``inputs_embeds`` where
    grad-accum hoisted the gather out of its scan (GSPMD's gather
    partitioning is unsound inside a while body), scaled, with the
    projected vision embeddings spliced over its first positions."""
    if "inputs_embeds" in batch:
        x = batch["inputs_embeds"]
    else:
        x = embed(params["embed"], batch["tokens"])
    x = _scale_embeddings(cfg, x)
    if cfg.vision_tokens and "vision_embeds" in batch:
        vis = batch["vision_embeds"] @ params["vision_proj"]
        nv = vis.shape[1]
        x = jnp.concatenate([vis.astype(x.dtype), x[:, nv:]], axis=1)
    enc_out = None
    if cfg.enc_layers:
        enc_out = encode(params, cfg, batch["src_embeds"].astype(x.dtype), remat)
    return x, enc_out


def _sequence(params, cfg: ModelConfig, batch: dict, remat: str, cache_len: int):
    """The stack over a whole sequence: (final hidden states, the layer
    caches stacked over periods at a capacity of ``max(cache_len, S + 1)``,
    the router aux loss of each period, the encoder's memory)."""
    P, n_periods, pos_kinds, pos_moe = period_info(cfg)
    x, enc_out = _embed_inputs(params, cfg, batch, remat)
    S = x.shape[1]
    cache_len = max(cache_len, S + 1)

    def mix(bp, kind, h, _):
        if kind == MAMBA:
            return mamba_lib.mamba_forward(bp["mamba"], h, cfg.mamba, cfg.d_model)
        acfg = _attn_cfg(cfg, kind)
        h, kv = attn_lib.attention(bp["attn"], h, cfg_attn=acfg)
        return h, _ring_from_prefill(kv, acfg, S, cache_len)

    def period_body(x, bps):
        caches, aux = {}, jnp.zeros((), jnp.float32)
        for j in range(P):
            x, caches[f"pos{j}"], a = _apply_block(
                bps[f"pos{j}"], cfg, pos_kinds[j], pos_moe[j], x, enc_out, mix,
                moe_lib.moe_apply)
            aux = aux + a
        # constrain the carry OUTPUT: this is the buffer remat saves per
        # period — sharded (data, model) it is 16x smaller than replicated
        return _constrain(x), (caches, aux)

    x, (caches, auxes) = jax.lax.scan(_remat_wrap(period_body, remat),
                                      _constrain(x), params["blocks"])
    return rmsnorm(params["final_norm"], x, cfg.norm_eps), caches, auxes, enc_out


def forward_train(params, cfg: ModelConfig, batch: dict, remat: str = "dots"):
    """Returns (logits, router aux loss).  The decode caches the sequence
    leaves go unused: under ``jax.jit`` their ops are dropped."""
    x, _, auxes, _ = _sequence(params, cfg, batch, remat, cache_len=0)
    return _logits(params, cfg, x), jnp.sum(auxes)


def loss_fn(params, cfg: ModelConfig, batch: dict, remat: str = "dots"):
    logits, aux = forward_train(params, cfg, batch, remat)
    ce = cross_entropy_loss(logits, batch["targets"], valid_vocab=cfg.vocab_size)
    aux_w = cfg.moe.aux_loss_weight if cfg.moe else 0.0
    return ce + aux_w * aux, {"ce": ce, "aux": aux}


def prefill(params, cfg: ModelConfig, batch: dict, remat: str = "dots",
            cache_len: int = 0):
    """Full-sequence forward producing (last-position logits, decode cache).

    The cache matches ``cache_specs(cfg, B, max(cache_len, S + 1))``:
    attention layers get their K/V (ring-rolled to window size for
    SWA/chunked kinds), SSD layers get {ssm state, conv tail}; enc-dec
    additionally stores the encoder memory.
    """
    S = batch["tokens"].shape[1]
    x, layer_caches, _, enc_out = _sequence(params, cfg, batch, remat, cache_len)
    logits = _logits(params, cfg, x[:, -1:])
    cache = {"layers": layer_caches, "pos": jnp.asarray(S, jnp.int32)}
    if cfg.enc_layers:
        cache["enc_memory"] = enc_out
    return logits, cache


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


def decode_step(params, cfg: ModelConfig, token: jax.Array, cache: dict):
    """token (B, 1) int32; cache from cache_specs/prefill. Returns (logits, cache).

    The stacked layer caches ride in the layer scan's carry, so each layer
    writes its token into them in place rather than the scan slicing every
    layer's cache out and stacking a new one."""
    P, n_periods, pos_kinds, pos_moe = period_info(cfg)
    pos = cache["pos"]
    x = _scale_embeddings(cfg, embed(params["embed"], token))
    enc_memory = cache.get("enc_memory")

    # Decode routes every token to its experts.  Train and prefill drop the
    # tokens over an expert's capacity, so where they drop any, an MoE
    # model's decode does not continue its prefill exactly.
    moe = partial(moe_lib.moe_ffn, no_drop=True)

    def period_body(carry, scanned):
        x, caches = carry
        bps, layer = scanned

        def mix(bp, kind, h, stack):
            # ``stack`` holds this block position's caches of every period;
            # the layer reads and writes its own, ``layer``, in place
            if kind == MAMBA:
                return mamba_lib.mamba_decode(
                    bp["mamba"], h, stack, layer, cfg.mamba, cfg.d_model)
            return attn_lib.attention_decode(
                bp["attn"], h, stack, pos, layer, cfg_attn=_attn_cfg(cfg, kind))

        caches = dict(caches)
        for j in range(P):
            x, caches[f"pos{j}"], _ = _apply_block(
                bps[f"pos{j}"], cfg, pos_kinds[j], pos_moe[j], x, enc_memory, mix,
                moe, caches[f"pos{j}"])
        return (x, caches), None

    (x, new_layer_caches), _ = jax.lax.scan(
        period_body, (x, cache["layers"]),
        (params["blocks"], jnp.arange(n_periods, dtype=jnp.int32)))
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _logits(params, cfg, x)
    new_cache = dict(cache)
    new_cache["layers"] = new_layer_caches
    new_cache["pos"] = pos + 1
    return logits, new_cache


