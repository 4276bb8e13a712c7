"""The program's interfaces the generators use, in one place: its model
configuration, its parameter layout and its training configuration."""
from __future__ import annotations

import numpy as np

from benchmarks.chip import flops


def model_config(c: dict):
    """``repro.configs.base.ModelConfig`` of a configuration file."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=c["name"], family="dense", citation=c["source"],
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        qkv_bias=bool(c.get("qkv_bias", False)),
        rope_theta=float(c["rope_theta"]),
        sliding_window=flops.window(c),
        mlp_act=c["hidden_act"], mlp_gated=True,
        tie_embeddings=bool(c.get("tie_word_embeddings", False)),
        norm_eps=float(c["rms_norm_eps"]), dtype=c["dtype"])


def train_config(cfg, t: dict, seed: int):
    """``TrainConfig`` of a training mix's data file."""
    from repro.configs.base import SyncConfig, TrainConfig

    opt = t["optimizer"]
    return TrainConfig(
        model=cfg, seq_len=t["seq_len"], global_batch=t["global_batch"],
        lr=opt["lr"], weight_decay=opt["weight_decay"],
        warmup_steps=opt["warmup_steps"], total_steps=opt["total_steps"],
        optimizer=opt["name"], grad_clip=opt["grad_clip"],
        sync=SyncConfig(**t["sync"]), remat=t["remat"], seed=seed)


def _leaf_name(path) -> tuple:
    """(name, stacked) of a parameter-tree path: ``tok``, ``unembed``,
    ``final_norm`` or, stacked over layers, the tensor's name
    (``norm1``/``norm2`` for the block's norm scales)."""
    keys = [getattr(k, "key", k) for k in path]
    if keys[0] == "blocks":
        return (keys[-2] if keys[-1] == "scale" else keys[-1]), True
    return ("final_norm" if keys[0] == "final_norm" else keys[-1]), False


def stacked_norms(tree):
    """``{leaf name: norm}`` of a parameter-shaped tree on the device, in
    one jitted program; a stacked leaf gives one norm per layer."""
    import jax
    import jax.numpy as jnp

    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    names = [_leaf_name(p) for p, _ in leaves]

    def f(xs):
        out = []
        for (_, stacked), x in zip(names, xs):
            x = x.astype(jnp.float32)
            axes = tuple(range(1, x.ndim)) if stacked else None
            out.append(jnp.sqrt(jnp.sum(x * x, axis=axes)))
        return out

    vals = jax.device_get(jax.jit(f)([x for _, x in leaves]))
    return flat_names(names, vals)


def host_change_norms(before, after) -> dict:
    """Per-leaf norms of ``after - before`` for two host copies of the
    parameters."""
    import jax

    names = [_leaf_name(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(before)[0]]
    a = jax.tree_util.tree_leaves(before)
    b = jax.tree_util.tree_leaves(after)
    vals = []
    for (name, stacked), x, y in zip(names, a, b):
        d = np.asarray(y, np.float32) - np.asarray(x, np.float32)
        d = d.reshape(d.shape[0], -1) if stacked else d.reshape(1, -1)
        vals.append(np.sqrt(np.sum(np.square(d, dtype=np.float64), axis=1)))
    return flat_names(names, vals)


def flat_names(names, vals) -> dict:
    """``{leaf name: norm}`` with the reference's names (``<layer>.<name>``
    for stacked leaves)."""
    out = {}
    for (name, stacked), v in zip(names, vals):
        if stacked:
            for layer, x in enumerate(np.asarray(v).reshape(-1)):
                out[f"{layer}.{name}"] = float(x)
        else:
            out[name] = float(np.asarray(v).reshape(-1)[0])
    return out
