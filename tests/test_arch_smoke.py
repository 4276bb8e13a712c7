"""Per-architecture smoke tests: reduced variant of each assigned family runs
one forward/train step on CPU with finite outputs and correct shapes, plus
prefill->decode consistency for every arch."""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_configs
from repro.models import (
    cache_specs, decode_step, forward_train, init_params, loss_fn, prefill)

ARCHS = [
    "llama4-scout-17b-a16e", "chameleon-34b", "qwen1.5-110b",
    "seamless-m4t-large-v2", "mamba2-2.7b", "qwen1.5-4b", "dbrx-132b",
    "jamba-1.5-large-398b", "h2o-danube-1.8b", "nemotron-4-15b",
    "granite-4.0-h-micro",
]


def _batch(cfg, B=2, S=16, key=jax.random.PRNGKey(0)):
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": toks, "targets": jnp.roll(toks, -1, axis=1)}
    if cfg.vision_tokens:
        batch["vision_embeds"] = 0.02 * jax.random.normal(
            key, (B, cfg.vision_tokens, cfg.d_model))
    if cfg.enc_layers:
        batch["src_embeds"] = 0.02 * jax.random.normal(
            key, (B, 12, cfg.enc_d_model or cfg.d_model))
    return batch


def test_all_archs_registered():
    assert sorted(ARCHS) == list_configs()


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_constraints(arch):
    r = get_config(arch).reduced()
    assert r.num_layers == 2 and r.d_model <= 512
    if r.moe:
        assert r.moe.num_experts <= 4


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_and_loss(arch):
    cfg = get_config(arch).reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    logits, aux = forward_train(params, cfg, batch)
    assert logits.shape == (2, 16, cfg.padded_vocab())
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    loss, parts = loss_fn(params, cfg, batch)
    assert bool(jnp.isfinite(loss)) and float(loss) > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_one_train_step_no_nans(arch):
    from repro.configs.base import TrainConfig
    from repro.training.steps import init_train_state, make_train_step

    cfg = get_config(arch).reduced()
    tc = TrainConfig(model=cfg, seq_len=16, global_batch=2, lr=1e-3,
                     warmup_steps=2, total_steps=4)
    params = init_params(jax.random.PRNGKey(0), cfg)
    state = init_train_state(jax.random.PRNGKey(1), params, tc, 1, 1)
    step = jax.jit(make_train_step(cfg, tc, 1, 1))
    state, metrics = step(state, _batch(cfg))
    assert bool(jnp.isfinite(metrics["loss"]))
    leaves = jax.tree_util.tree_leaves(state.params)
    assert all(bool(jnp.all(jnp.isfinite(l.astype(jnp.float32)))) for l in leaves)


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_forward(arch):
    """Prefill then two decode steps give the logits of the full forward:
    the block's three uses (train, prefill, decode) agree for every
    architecture."""
    cfg = get_config(arch).reduced()
    if cfg.moe:  # drop-free reference for exactness
        cfg = replace(cfg, moe=replace(cfg.moe,
                                       capacity_factor=float(cfg.moe.num_experts) / cfg.moe.top_k))
    params = init_params(jax.random.PRNGKey(1), cfg)
    B, S = 2, 20
    key = jax.random.PRNGKey(7)
    toks = jax.random.randint(key, (B, S + 2), 0, cfg.vocab_size)
    batch = _batch(cfg, B, S, key)
    batch["tokens"] = toks[:, :S]
    full = dict(batch)
    full["tokens"] = toks
    logits_full, _ = forward_train(params, cfg, full)
    _, cache = prefill(params, cfg, batch, cache_len=S + 3)
    for t in range(S, S + 2):
        lg, cache = decode_step(params, cfg, toks[:, t:t + 1], cache)
        a = np.asarray(logits_full[:, t, :], np.float32)
        b = np.asarray(lg[:, 0, :], np.float32)
        assert np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-9) < 1e-4


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "llama4-scout-17b-a16e"])
def test_decode_matches_forward_past_the_window(arch):
    """40 decode steps after a 6-token prompt wrap the reduced ring of 16
    slots twice and cross the chunk boundaries at 16 and 32; every step's
    logits match the full forward's."""
    cfg = get_config(arch).reduced()
    assert 16 in (cfg.sliding_window, cfg.attn_chunk)
    if cfg.moe:  # drop-free reference for exactness
        cfg = replace(cfg, moe=replace(cfg.moe,
                                       capacity_factor=float(cfg.moe.num_experts) / cfg.moe.top_k))
    params = init_params(jax.random.PRNGKey(1), cfg)
    B, S, steps = 2, 6, 40
    key = jax.random.PRNGKey(7)
    toks = jax.random.randint(key, (B, S + steps), 0, cfg.vocab_size)
    batch = _batch(cfg, B, S, key)
    batch["tokens"] = toks[:, :S]
    full = dict(batch)
    full["tokens"] = toks
    logits_full, _ = forward_train(params, cfg, full)
    _, cache = prefill(params, cfg, batch, cache_len=S + steps)
    step = jax.jit(lambda c, t: decode_step(params, cfg, t, c))
    for t in range(S, S + steps):
        lg, cache = step(cache, toks[:, t:t + 1])
        a = np.asarray(logits_full[:, t, :], np.float32)
        b = np.asarray(lg[:, 0, :], np.float32)
        assert np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-9) < 1e-4, t


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "llama4-scout-17b-a16e"])
def test_windowed_cache_is_bounded(arch):
    """SWA/chunked archs must hold a window-sized cache, not seq_len."""
    cfg = get_config(arch)
    specs = cache_specs(cfg, batch=1, seq_len=524288)
    for j, kind in enumerate(cfg.layer_kinds()[: len(specs["layers"])]):
        leaf = specs["layers"][f"pos{j}"]
        if "k" in leaf:
            S = leaf["k"].shape[1]   # (n_periods, S, B, KV, hd)
            if kind == "attn_swa":
                assert S <= cfg.sliding_window
            elif kind == "attn_chunk":
                assert S <= cfg.attn_chunk


def test_param_count_matches_init():
    """Analytic param_count agrees with actual init within 1%."""
    for arch in ["qwen1.5-4b", "mamba2-2.7b", "dbrx-132b"]:
        cfg = get_config(arch).reduced()
        params = init_params(jax.random.PRNGKey(0), cfg)
        actual = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params))
        assert abs(actual - cfg.param_count()) / actual < 0.01
