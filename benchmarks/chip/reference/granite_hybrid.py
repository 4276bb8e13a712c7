"""Plain float32 reference of granite-4.0-h: Mamba-2 mixers beside NoPE
attention, every layer followed by a gated SiLU MLP.

Written from the published equations (Dao & Gu 2024, arXiv:2405.21060, and
the granitemoehybrid block of the model's ``config.json``), in
``jax.numpy`` in float32 with every matrix product at precision HIGHEST,
one sequence and one layer at a time, with no cache, no chunking and no
kernels.  It imports nothing of the program under test.

A layer on a sequence x (S, d), with m = ``residual_multiplier``::

    x = x + m * mixer(rmsnorm(x))
    x = x + m * w_out(silu(rmsnorm(x) w_gate) * (rmsnorm(x) w_in))

The Mamba-2 mixer: ``in_proj`` gives z, xBC and dt; xBC goes through a
causal depthwise conv1d with bias and then SiLU, and splits into x (heads
of ``mamba_d_head``), B and C (``mamba_n_groups`` groups of
``mamba_d_state``); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
then, one position after another, with the state h (heads, d_head, d_state)
starting at zero::

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T
    y_t = C_t h_t + D x_t

and the output is ``out_proj(rmsnorm(y * silu(z)))``, the gated RMSNorm
over the whole inner width (one group).  The attention mixer is causal GQA
with no positional encoding and the softmax scale ``attention_multiplier``.
Token embeddings are multiplied by ``embedding_multiplier``; the head is
the embedding transposed (tied), after a final RMSNorm, and the logits are
divided by ``logits_scaling``.

Departures from the published model: the weights are drawn from a seed
(:func:`bench_program_tree`), not the released checkpoint, with the tied
embedding at a standard deviation of ``EMBED_STD`` (see there);
``head_dim`` is hidden / heads (the config gives none); nothing else.

``numerics`` is ``"f32"`` for the reference and ``"fp8"`` for the check's
control: both operands of every matrix product (projections, attention,
MLP, head) rounded to float8_e4m3fn under a per-tensor scale; the conv and
the recurrence stay in float32.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.reference.dense_lm import (BIAS_STD, HEAD_LAYER, HIGHEST,
                                                NORM_STD, _normal_bf16, as_f32,
                                                product, rmsnorm)

MAMBA = "mamba"
ATTENTION = "attention"


@dataclass(frozen=True)
class Shapes:
    d: int
    ff: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    layer_types: tuple      # "mamba" or "attention", one per layer
    ssm_heads: int
    ssm_head_dim: int
    d_state: int
    groups: int
    d_conv: int
    chunk: int              # the published chunk (the program's prefill)
    eps: float
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float
    logits_scaling: float

    @classmethod
    def from_config(cls, c: dict) -> "Shapes":
        return cls(
            d=c["hidden_size"], ff=c["shared_intermediate_size"],
            heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
            head_dim=c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"],
            vocab=c["vocab_size"], layer_types=tuple(c["layer_types"]),
            ssm_heads=c["mamba_n_heads"], ssm_head_dim=c["mamba_d_head"],
            d_state=c["mamba_d_state"], groups=c["mamba_n_groups"],
            d_conv=c["mamba_d_conv"], chunk=c["mamba_chunk_size"],
            eps=float(c["rms_norm_eps"]),
            embedding_multiplier=float(c["embedding_multiplier"]),
            residual_multiplier=float(c["residual_multiplier"]),
            attention_multiplier=float(c["attention_multiplier"]),
            logits_scaling=float(c["logits_scaling"]))

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    @property
    def period(self) -> int:
        """Length of the shortest pattern the layer types repeat."""
        n = self.layers
        return next(p for p in range(1, n + 1) if n % p == 0 and
                    self.layer_types == self.layer_types[:p] * (n // p))

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.groups * self.d_state

    def layer_shapes(self, kind: str) -> dict:
        """Shape and stored dtype of each tensor of a layer of ``kind``."""
        bf = jnp.bfloat16
        out = {"norm1": ((self.d,), bf), "norm2": ((self.d,), bf),
               "w_in": ((self.d, self.ff), bf), "w_gate": ((self.d, self.ff), bf),
               "w_out": ((self.ff, self.d), bf)}
        if kind == ATTENTION:
            q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
            out.update(wq=((self.d, q), bf), wk=((self.d, kv), bf),
                       wv=((self.d, kv), bf), wo=((q, self.d), bf))
        else:
            h = self.ssm_heads
            in_dim = self.d_inner + self.conv_dim + h
            out.update(
                in_proj=((self.d, in_dim), bf),
                conv_w=((self.d_conv, self.conv_dim), bf),
                conv_b=((self.conv_dim,), bf),
                a_log=((h,), jnp.float32), dt_bias=((h,), jnp.float32),
                D=((h,), jnp.float32), mixer_norm=((self.d_inner,), bf),
                out_proj=((self.d_inner, self.d), bf))
        return out


# ---------------------------------------------------------------------------
# the benchmark's serving weights, in the program's layout
# ---------------------------------------------------------------------------
# Every tensor is drawn from its own key, fold_in(fold_in(seed key, layer),
# tensor index); norm scales and the conv bias are not trivial, so the check
# sees them.  A and dt follow the published initialisation: A uniform in
# [1, 16], dt log-uniform in [1e-3, 0.1] with dt_bias its inverse softplus.
_TENSORS = ("norm1", "norm2", "w_in", "w_gate", "w_out", "wq", "wk", "wv",
            "wo", "in_proj", "conv_w", "conv_b", "a_log", "dt_bias", "D",
            "mixer_norm", "out_proj")
DT_MIN, DT_MAX = 1e-3, 0.1
# The tied embedding's standard deviation.  The embedding enters the
# residual stream 12 times over and the same matrix makes the logits, so at
# the 0.02 the dense reference draws, a random model copies its input: at
# the published widths its input token tops 98% of the rows, 6.7 row
# standard deviations above the mean and 2.3 above the next, and greedy
# output repeats the prompt's last token whatever the layers compute (on
# the chip the float8 control then read 0.0 on one of two seeds).  At 0.005
# the embedding is 2.7 of the residual's norm of 72, no row copies, and the
# best logit stands 4.3 deviations up, as in a generic row; the float8
# control reads 1.87 over 2 x 48 positions (CPU, float32 at HIGHEST).
EMBED_STD = 0.005


def bench_layer(sh: Shapes, seed_key, layer, kind: str) -> dict:
    """Layer ``layer`` (an int or a traced int) of the serving weights."""
    k = jax.random.fold_in(seed_key, layer)
    out = {}
    for i, name in enumerate(_TENSORS):
        if name not in sh.layer_shapes(kind):
            continue
        shape, dtype = sh.layer_shapes(kind)[name]
        ki = jax.random.fold_in(k, i)
        if name in ("norm1", "norm2", "mixer_norm"):
            x = 1.0 + NORM_STD * jax.random.normal(ki, shape)
        elif name == "conv_b":
            x = BIAS_STD * jax.random.normal(ki, shape)
        elif name == "conv_w":
            x = jax.random.normal(ki, shape) / math.sqrt(sh.d_conv)
        elif name == "a_log":
            x = jnp.log(jax.random.uniform(ki, shape, minval=1.0, maxval=16.0))
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(ki, shape, minval=math.log(DT_MIN),
                                            maxval=math.log(DT_MAX)))
            x = dt + jnp.log(-jnp.expm1(-dt))
        elif name == "D":
            x = 1.0 + NORM_STD * jax.random.normal(ki, shape)
        else:
            out[name] = _normal_bf16(ki, shape, 1.0 / math.sqrt(shape[0]))
            continue
        out[name] = x.astype(dtype)
    return out


def bench_head(sh: Shapes, seed_key) -> dict:
    k = jax.random.fold_in(seed_key, HEAD_LAYER)
    return {
        "tok": _normal_bf16(jax.random.fold_in(k, 0), (sh.vocab, sh.d),
                            EMBED_STD),
        "final_norm": (1.0 + NORM_STD * jax.random.normal(
            jax.random.fold_in(k, 2), (sh.d,))).astype(jnp.bfloat16),
    }


_MIXER = {MAMBA: ("in_proj", "conv_w", "conv_b", "a_log", "dt_bias", "D",
                  "out_proj"),
          ATTENTION: ("wq", "wk", "wv", "wo")}


def bench_program_tree(sh: Shapes, seed_key) -> dict:
    """The serving weights in the program's parameter layout:
    ``embed/tok`` (tied head), ``final_norm/scale`` and ``blocks/pos<j>/...``
    for each position ``j`` of the layer pattern, stacked over its periods.
    Call under ``jax.jit``: one program makes them all on the device."""
    p, n = sh.period, sh.layers // sh.period
    blocks = {}
    for j in range(p):
        kind = sh.layer_types[j]
        lw = jax.vmap(lambda q: bench_layer(sh, seed_key, q * p + j, kind))(
            jnp.arange(n))
        block = {"norm1": {"scale": lw["norm1"]},
                 "norm2": {"scale": lw["norm2"]},
                 "mlp": {n_: lw[n_] for n_ in ("w_in", "w_gate", "w_out")}}
        if kind == ATTENTION:
            block["attn"] = {n_: lw[n_] for n_ in _MIXER[ATTENTION]}
        else:
            block["mamba"] = {n_: lw[n_] for n_ in _MIXER[MAMBA]}
            block["mamba"]["norm"] = {"scale": lw["mixer_norm"]}
        blocks[f"pos{j}"] = block
    head = bench_head(sh, seed_key)
    return {"embed": {"tok": head["tok"]},
            "final_norm": {"scale": head["final_norm"]}, "blocks": blocks}


def block_layer(block: dict, q) -> dict:
    """Period ``q`` (an int or a traced int) of one position's stacked
    block, by tensor name."""
    mixer = block.get("attn") or block["mamba"]
    norms = {"norm1": block["norm1"], "norm2": block["norm2"],
             "mixer_norm": mixer.get("norm")}       # None for attention
    out = {n: s["scale"][q] for n, s in norms.items() if s is not None}
    out.update({n: a[q] for n, a in block["mlp"].items()})
    out.update({n: a[q] for n, a in mixer.items() if n != "norm"})
    return out


def layer_of(sh: Shapes, tree: dict, layer: int) -> dict:
    """Layer ``layer`` of a tree in the program's layout, by tensor name."""
    return block_layer(tree["blocks"][f"pos{layer % sh.period}"],
                       layer // sh.period)


def head_of(tree: dict) -> dict:
    return {"tok": tree["embed"]["tok"],
            "final_norm": tree["final_norm"]["scale"]}


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------
def ssm_scan(sh: Shapes, x, dt, B, C, a_log, D, state_dtype=jnp.float32):
    """The Mamba-2 recurrence, one position after another.  x (S, H, P),
    dt (S, H) after softplus, B and C (S, G, N).  Returns y (S, H, P).
    ``state_dtype`` is the type the state is held in between positions."""
    A = -jnp.exp(a_log)
    rep = sh.ssm_heads // sh.groups

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        b_h = jnp.repeat(b_t, rep, axis=0)                 # (H, N)
        c_h = jnp.repeat(c_t, rep, axis=0)
        h = (jnp.exp(dt_t * A)[:, None, None] * h.astype(jnp.float32)
             + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        y = jnp.einsum("hpn,hn->hp", h, c_h, precision=HIGHEST) + D[:, None] * x_t
        return h.astype(state_dtype), y

    h0 = jnp.zeros((sh.ssm_heads, sh.ssm_head_dim, sh.d_state), state_dtype)
    _, y = jax.lax.scan(step, h0, (x, dt, B, C))
    return y


def mamba_mixer(sh: Shapes, mm, lw: dict, h, state_dtype=jnp.float32):
    s = h.shape[0]
    di, gn = sh.d_inner, sh.groups * sh.d_state
    zxbcdt = mm("sd,de->se", h, lw["in_proj"])
    z, xbc, dt = jnp.split(zxbcdt, [di, di + sh.conv_dim], axis=-1)
    k = sh.d_conv
    pad = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    conv = sum(pad[i:i + s] * lw["conv_w"][i] for i in range(k)) + lw["conv_b"]
    xbc = jax.nn.silu(conv)
    x, b, c = jnp.split(xbc, [di, di + gn], axis=-1)
    dt = jax.nn.softplus(dt + lw["dt_bias"])
    y = ssm_scan(sh, x.reshape(s, sh.ssm_heads, sh.ssm_head_dim), dt,
                 b.reshape(s, sh.groups, sh.d_state),
                 c.reshape(s, sh.groups, sh.d_state), lw["a_log"], lw["D"],
                 state_dtype)
    y = rmsnorm(y.reshape(s, di) * jax.nn.silu(z), lw["mixer_norm"], sh.eps)
    return mm("se,ed->sd", y, lw["out_proj"])


def attention_mixer(sh: Shapes, mm, lw: dict, h):
    """Causal GQA with no positional encoding; one KV head's group of query
    heads at a time."""
    s = h.shape[0]
    g = sh.heads // sh.kv_heads
    q = mm("sd,de->se", h, lw["wq"]).reshape(s, sh.kv_heads, g, sh.head_dim)
    k = mm("sd,de->se", h, lw["wk"]).reshape(s, sh.kv_heads, sh.head_dim)
    v = mm("sd,de->se", h, lw["wv"]).reshape(s, sh.kv_heads, sh.head_dim)
    bias = jnp.asarray(np.where(np.tril(np.ones((s, s), bool)), 0.0, -np.inf),
                       jnp.float32)

    @jax.checkpoint
    def one_group(args):
        qh, kh, vh = args                      # (g, S, hd), (S, hd), (S, hd)
        scores = mm("gqd,kd->gqk", qh, kh) * sh.attention_multiplier + bias
        return mm("gqk,kd->gqd", jax.nn.softmax(scores, axis=-1), vh)

    out = jax.lax.map(one_group, (q.transpose(1, 2, 0, 3), k.transpose(1, 0, 2),
                                  v.transpose(1, 0, 2)))   # (KV, g, S, hd)
    out = out.transpose(2, 0, 1, 3).reshape(s, sh.heads * sh.head_dim)
    return mm("se,ed->sd", out, lw["wo"])


def layer(sh: Shapes, numerics: str, kind: str, lw: dict, x,
          state_dtype=jnp.float32):
    """One layer of ``kind`` on one sequence x (S, d)."""
    mm = product(numerics)
    m = sh.residual_multiplier
    h = rmsnorm(x, lw["norm1"], sh.eps)
    h = (mamba_mixer(sh, mm, lw, h, state_dtype) if kind == MAMBA
         else attention_mixer(sh, mm, lw, h))
    x = x + m * h
    h = rmsnorm(x, lw["norm2"], sh.eps)
    gate = mm("sd,df->sf", h, lw["w_gate"])
    up = mm("sd,df->sf", h, lw["w_in"])
    return x + m * mm("sf,fd->sd", jax.nn.silu(gate) * up, lw["w_out"])


def embed(sh: Shapes, head: dict, tokens):
    return head["tok"][tokens] * sh.embedding_multiplier


def logits(sh: Shapes, numerics: str, head: dict, x):
    h = rmsnorm(x, head["final_norm"], sh.eps)
    return product(numerics)("sd,vd->sv", h, head["tok"]) / sh.logits_scaling


def forward(sh: Shapes, numerics: str, params: dict, tokens,
            state_dtype=jnp.float32):
    """Logits (S, V) of one sequence of token ids (S,); ``params`` holds
    ``tok``, ``final_norm`` and ``layers``, a list of per-layer dicts."""
    x = embed(sh, params, tokens)
    for kind, lw in zip(sh.layer_types, params["layers"]):
        x = layer(sh, numerics, kind, lw, x, state_dtype)
    return logits(sh, numerics, params, x)


def sequence_loss(sh: Shapes, params: dict, row):
    """Mean next-token cross-entropy of one row of S+1 token ids."""
    lg = forward(sh, "f32", params, row[:-1])
    gold = jnp.take_along_axis(lg, row[1:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - gold)


@lru_cache(maxsize=None)
def _layer_fn(sh: Shapes, numerics: str, kind: str):
    return jax.jit(partial(layer, sh, numerics, kind))


@lru_cache(maxsize=None)
def _logits_fn(sh: Shapes, numerics: str):
    return jax.jit(partial(logits, sh, numerics))


def served_logits(sh: Shapes, numerics: str, layer_weights, head: dict,
                  sequences, served_from: int) -> list:
    """Logits (n, V) of the served positions of each sequence.

    Each sequence is a prompt of ``served_from`` tokens followed by the
    tokens served for it; position ``i``'s logits predict token ``i + 1``.
    ``layer_weights(l)`` gives layer ``l``'s weights (float32), made one
    layer at a time so that the reference fits beside nothing else."""
    xs = [embed(sh, head, jnp.asarray(seq[:-1])) for seq in sequences]
    for l, kind in enumerate(sh.layer_types):
        w = layer_weights(l)
        step = _layer_fn(sh, numerics, kind)
        xs = [step(w, x) for x in xs]
        del w
    out = _logits_fn(sh, numerics)
    return [out(head, x)[served_from - 1:] for x in xs]


def reference_rows(sh: Shapes, seed: int, sequences, served_from: int,
                   numerics: str) -> list:
    """:func:`served_logits` from the serving weights made again from the
    (31-bit) ``seed``, one layer at a time taken out of the tree."""
    tree = jax.jit(partial(bench_program_tree, sh))(jax.random.PRNGKey(seed))
    take = jax.jit(block_layer)       # one program per kind of block
    p = sh.period

    def weights(l):
        return as_f32(take(tree["blocks"][f"pos{l % p}"], l // p))

    with jax.default_matmul_precision("highest"):
        return served_logits(sh, numerics, weights, as_f32(head_of(tree)),
                             sequences, served_from)
