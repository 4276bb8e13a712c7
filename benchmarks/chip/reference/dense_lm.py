"""Plain float32 reference of the dense decoder the configurations describe.

The Llama/Mistral/Qwen2 block as published: RMSNorm before attention and
before the MLP, rotary embeddings (rotate-half), grouped-query causal
attention with an optional sliding window, optional biases on Q, K and V,
a gated SiLU MLP, a final RMSNorm and an untied output head.  Written in
``jax.numpy`` in float32 with every product at precision HIGHEST, one
sequence and one layer at a time, with no cache and no kernels.  It imports
nothing of the program under test.

Weights come from a seed in one of two ways:

* :func:`init_like_program` draws what the program's training init draws
  from the same key (the same splits, distributions and bfloat16
  rounding), so the reference starts where ``train()`` starts;
* :func:`bench_program_tree` makes the benchmark's own weights for serving
  in the program's layout; the program is handed them, and the reference
  makes them again with the same jitted call and reads one layer at a time
  (:func:`layer_of`), so both compute with the same bits.

Weights are made in bfloat16, the type the configurations store them in,
and computed with in float32 (:func:`as_f32`).

``numerics`` is ``"f32"`` for the reference and ``"fp8"`` for the check's
control: the same computation with both operands of every product rounded
to float8_e4m3fn under a per-tensor scale, in the backward pass too.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
MATRICES = ("wq", "wk", "wv", "wo", "w_in", "w_gate", "w_out")
BIASES = ("bq", "bk", "bv")


@dataclass(frozen=True)
class Shapes:
    d: int
    ff: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    layers: int
    window: int          # 0: full causal attention
    rope_theta: float
    eps: float
    qkv_bias: bool

    @classmethod
    def from_config(cls, c: dict) -> "Shapes":
        return cls(
            d=c["hidden_size"], ff=c["intermediate_size"],
            heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
            head_dim=c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"],
            vocab=c["vocab_size"], layers=c["num_hidden_layers"],
            window=(c.get("sliding_window") or 0)
            if c.get("use_sliding_window", True) else 0,
            rope_theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
            qkv_bias=bool(c.get("qkv_bias", False)))

    def layer_shapes(self) -> dict:
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        out = {"norm1": (self.d,), "wq": (self.d, q), "wk": (self.d, kv),
               "wv": (self.d, kv), "wo": (q, self.d), "norm2": (self.d,),
               "w_in": (self.d, self.ff), "w_gate": (self.d, self.ff),
               "w_out": (self.ff, self.d)}
        if self.qkv_bias:
            out.update(bq=(q,), bk=(kv,), bv=(kv,))
        return out


def as_f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


# ---------------------------------------------------------------------------
# weights (made in bfloat16, the type they are stored in)
# ---------------------------------------------------------------------------
def _normal_bf16(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(jnp.bfloat16)


def init_like_program(sh: Shapes, seed_key) -> dict:
    """The weights ``train()`` makes from ``PRNGKey(seed)``: its init draws
    ``key, k = split(key)``; ``k`` splits into embedding, blocks and two
    unused keys; each layer's key splits six ways (attention from the
    first, MLP from the fourth); a matrix of fan-in ``n`` is N(0, 1/n),
    the embedding and head N(0, 0.02^2), norms one, biases zero."""
    _, kinit = jax.random.split(seed_key)
    k_embed, k_blocks, _, _ = jax.random.split(kinit, 4)
    ke = jax.random.split(k_embed, 2)
    params = {
        "tok": _normal_bf16(ke[0], (sh.vocab, sh.d), 0.02),
        "unembed": _normal_bf16(ke[1], (sh.d, sh.vocab), 0.02),
        "final_norm": jnp.ones((sh.d,), jnp.bfloat16),
        "layers": [],
    }
    shapes = sh.layer_shapes()
    block_keys = jax.random.split(k_blocks, sh.layers)
    for l in range(sh.layers):
        ks = jax.random.split(block_keys[l], 6)
        ka = jax.random.split(ks[0], 4)
        km = jax.random.split(ks[3], 3)
        lw = {}
        for name, key in zip(("wq", "wk", "wv", "wo"), ka):
            shape = shapes[name]
            lw[name] = _normal_bf16(key, shape, 1.0 / math.sqrt(shape[0]))
        for name, key in zip(("w_in", "w_out", "w_gate"), km):
            shape = shapes[name]
            lw[name] = _normal_bf16(key, shape, 1.0 / math.sqrt(shape[0]))
        for name in BIASES if sh.qkv_bias else ():
            lw[name] = jnp.zeros(shapes[name], jnp.bfloat16)
        lw["norm1"] = jnp.ones((sh.d,), jnp.bfloat16)
        lw["norm2"] = jnp.ones((sh.d,), jnp.bfloat16)
        params["layers"].append(lw)
    return params


# The benchmark's serving weights: every tensor drawn from its own key,
# fold_in(fold_in(seed key, layer), tensor index).  Biases and norm scales
# are not trivial, so the check sees them.
_TENSORS = ("norm1", "wq", "wk", "wv", "wo", "norm2", "w_in", "w_gate",
            "w_out", "bq", "bk", "bv")
BIAS_STD = 0.1
NORM_STD = 0.1
HEAD_LAYER = 1 << 20      # fold_in index of the embedding, head and norm


def bench_layer(sh: Shapes, seed_key, layer) -> dict:
    """Layer ``layer`` (an int or a traced int) of the serving weights."""
    k = jax.random.fold_in(seed_key, layer)
    out = {}
    for i, name in enumerate(_TENSORS):
        shape = sh.layer_shapes().get(name)
        if shape is None:
            continue
        ki = jax.random.fold_in(k, i)
        if name.startswith("norm"):
            out[name] = (1.0 + NORM_STD * jax.random.normal(ki, shape)
                         ).astype(jnp.bfloat16)
        elif name in BIASES:
            out[name] = _normal_bf16(ki, shape, BIAS_STD)
        else:
            out[name] = _normal_bf16(ki, shape, 1.0 / math.sqrt(shape[0]))
    return out


def bench_head(sh: Shapes, seed_key) -> dict:
    k = jax.random.fold_in(seed_key, HEAD_LAYER)
    return {
        "tok": _normal_bf16(jax.random.fold_in(k, 0), (sh.vocab, sh.d), 0.02),
        "unembed": _normal_bf16(jax.random.fold_in(k, 1), (sh.d, sh.vocab), 0.02),
        "final_norm": (1.0 + NORM_STD * jax.random.normal(
            jax.random.fold_in(k, 2), (sh.d,))).astype(jnp.bfloat16),
    }


def layer_of(tree: dict, layer: int) -> dict:
    """Layer ``layer`` of a tree in the program's layout, by tensor name."""
    b = tree["blocks"]["pos0"]
    out = {"norm1": b["norm1"]["scale"][layer],
           "norm2": b["norm2"]["scale"][layer]}
    out.update({n: a[layer] for n, a in b["attn"].items()})
    out.update({n: a[layer] for n, a in b["mlp"].items()})
    return out


def head_of(tree: dict) -> dict:
    return {"tok": tree["embed"]["tok"], "unembed": tree["embed"]["unembed"],
            "final_norm": tree["final_norm"]["scale"]}


def bench_program_tree(sh: Shapes, seed_key) -> dict:
    """The serving weights in the program's parameter layout:
    ``embed/{tok,unembed}``, ``final_norm/scale`` and ``blocks/pos0/...``
    stacked over layers.  Call under ``jax.jit``: one program makes them
    all on the device."""
    layers = jax.vmap(lambda l: bench_layer(sh, seed_key, l))(
        jnp.arange(sh.layers))
    head = bench_head(sh, seed_key)
    return {
        "embed": {"tok": head["tok"], "unembed": head["unembed"]},
        "final_norm": {"scale": head["final_norm"]},
        "blocks": {"pos0": {
            "norm1": {"scale": layers["norm1"]},
            "attn": {n: layers[n] for n in ("wq", "wk", "wv", "wo") + BIASES
                     if n in layers},
            "norm2": {"scale": layers["norm2"]},
            "mlp": {n: layers[n] for n in ("w_in", "w_gate", "w_out")},
        }},
    }


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------
def _q8(x):
    s = jnp.max(jnp.abs(x)) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _einsum(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum8(eq, a, b):
    return _einsum(eq, _q8(a), _q8(b))


def _einsum8_fwd(eq, a, b):
    qa, qb = _q8(a), _q8(b)
    return _einsum(eq, qa, qb), (qa, qb)


def _einsum8_bwd(eq, res, g):
    qa, qb = res
    _, vjp = jax.vjp(lambda x, y: _einsum(eq, x, y), qa, qb)
    return vjp(_q8(g))


_einsum8.defvjp(_einsum8_fwd, _einsum8_bwd)


def product(numerics: str):
    if numerics == "f32":
        return _einsum
    if numerics == "fp8":
        return _einsum8
    raise ValueError(f"unknown numerics {numerics!r}")


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------
def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x (S, H, hd) at positions 0..S-1; the pair (i, i + hd/2) rotates."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(sh: Shapes, mm, q, k, v):
    """Causal (windowed) attention of one sequence; q (S, H, hd), k and v
    (S, KV, hd).  One KV head's group of query heads at a time."""
    s = q.shape[0]
    g = sh.heads // sh.kv_heads
    qi = np.arange(s)[:, None]
    ki = np.arange(s)[None, :]
    allowed = ki <= qi
    if sh.window:
        allowed &= (qi - ki) < sh.window
    bias = jnp.asarray(np.where(allowed, 0.0, -np.inf), jnp.float32)
    qg = q.reshape(s, sh.kv_heads, g, sh.head_dim).transpose(1, 2, 0, 3)
    kg, vg = k.transpose(1, 0, 2), v.transpose(1, 0, 2)

    @jax.checkpoint
    def one_group(args):
        qh, kh, vh = args                      # (g, S, hd), (S, hd), (S, hd)
        scores = mm("gqd,kd->gqk", qh, kh) / math.sqrt(sh.head_dim) + bias
        p = jax.nn.softmax(scores, axis=-1)
        return mm("gqk,kd->gqd", p, vh)

    out = jax.lax.map(one_group, (qg, kg, vg))  # (KV, g, S, hd)
    return out.transpose(2, 0, 1, 3).reshape(s, sh.heads * sh.head_dim)


def layer(sh: Shapes, numerics: str, lw: dict, x):
    """One decoder layer on one sequence x (S, d)."""
    mm = product(numerics)
    s = x.shape[0]
    h = rmsnorm(x, lw["norm1"], sh.eps)
    q, k, v = (mm("sd,de->se", h, lw[w]) for w in ("wq", "wk", "wv"))
    if sh.qkv_bias:
        q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
    q = rope(q.reshape(s, sh.heads, sh.head_dim), sh.rope_theta)
    k = rope(k.reshape(s, sh.kv_heads, sh.head_dim), sh.rope_theta)
    v = v.reshape(s, sh.kv_heads, sh.head_dim)
    x = x + mm("se,ed->sd", attention(sh, mm, q, k, v), lw["wo"])
    h = rmsnorm(x, lw["norm2"], sh.eps)
    gate = mm("sd,df->sf", h, lw["w_gate"])
    up = mm("sd,df->sf", h, lw["w_in"])
    return x + mm("sf,fd->sd", jax.nn.silu(gate) * up, lw["w_out"])


def logits(sh: Shapes, numerics: str, head: dict, x):
    h = rmsnorm(x, head["final_norm"], sh.eps)
    return product(numerics)("sd,dv->sv", h, head["unembed"])


def forward(sh: Shapes, numerics: str, params: dict, tokens):
    """Logits (S, V) of one sequence of token ids (S,)."""
    x = params["tok"][tokens]
    for lw in params["layers"]:
        x = jax.checkpoint(partial(layer, sh, numerics))(lw, x)
    return logits(sh, numerics, params, x)


def sequence_loss(sh: Shapes, numerics: str, params: dict, row):
    """Mean next-token cross-entropy of one row of S+1 token ids."""
    lg = forward(sh, numerics, params, row[:-1])
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, row[1:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


# ---------------------------------------------------------------------------
# training: AdamW steps as the configuration states them
# ---------------------------------------------------------------------------
def learning_rate(opt: dict, step: int) -> float:
    """Linear warm-up and cosine decay to ``final_frac`` of the peak; the
    optimizer's first step is ``step`` 1."""
    warm = min(1.0, (step + 1) / max(1, opt["warmup_steps"]))
    span = max(1, opt["total_steps"] - opt["warmup_steps"])
    prog = min(max((step - opt["warmup_steps"]) / span, 0.0), 1.0)
    frac = opt.get("final_frac", 0.1)
    cos = frac + (1 - frac) * 0.5 * (1 + math.cos(math.pi * prog))
    return opt["lr"] * warm * cos


def flat(params: dict) -> dict:
    """``{leaf name: array}``: ``tok``, ``unembed``, ``final_norm`` and
    ``<layer>.<tensor>``."""
    out = {k: params[k] for k in ("tok", "unembed", "final_norm")}
    for l, lw in enumerate(params["layers"]):
        out.update({f"{l}.{n}": a for n, a in lw.items()})
    return out


def train_steps(sh: Shapes, numerics: str, params: dict, batches, opt: dict):
    """Run ``len(batches)`` optimizer steps from ``params`` (bfloat16
    values) on batches of rows (B, S+1); gradients are summed one row at a
    time, in float32.

    Returns the loss of each step and, by leaf, the norms of the first
    step's clipped gradient and of the parameters' change over the steps.
    Only norms leave the device: the parameters (bfloat16, as stored), the
    two moments and one gradient sum are what it holds."""
    accumulate = jax.jit(partial(_accumulate, sh, numerics), donate_argnums=2)
    update = jax.jit(partial(_adamw, opt), donate_argnums=(0, 1, 2))
    leaf_norms = jax.jit(lambda t, k: {n: jnp.sqrt(jnp.sum(jnp.square(a * k)))
                                       for n, a in t.items()})
    cur = {n: a.astype(jnp.bfloat16) for n, a in flat(params).items()}
    start = {n: jnp.copy(a) for n, a in cur.items()}
    mu = {n: jnp.zeros(a.shape, jnp.float32) for n, a in cur.items()}
    nu = {n: jnp.zeros(a.shape, jnp.float32) for n, a in cur.items()}
    losses, first = [], None
    for i, batch in enumerate(batches):
        rows = jnp.asarray(batch)
        n_rows = rows.shape[0]
        gsum = {n: jnp.zeros(a.shape, jnp.float32) for n, a in cur.items()}
        total = 0.0
        for r in range(n_rows):
            loss, gsum = accumulate(cur, rows[r], gsum)
            total += float(loss)
        norms_ = {n: float(v) for n, v in leaf_norms(gsum, 1.0 / n_rows).items()}
        gnorm = math.sqrt(sum(v * v for v in norms_.values()))
        factor = min(1.0, opt["grad_clip"] / (gnorm + 1e-9)) / n_rows
        if first is None:
            first = {n: v * factor * n_rows for n, v in norms_.items()}
        cur, mu, nu = update(cur, mu, nu, gsum, factor,
                             learning_rate(opt, i + 1), i + 1)
        losses.append(total / n_rows)
    change = {n: float(jnp.linalg.norm((cur[n].astype(jnp.float32)
                                        - start[n].astype(jnp.float32)).ravel()))
              for n in cur}
    return losses, first, change


def _accumulate(sh, numerics, p, row, gsum):
    loss, g = jax.value_and_grad(
        lambda q: sequence_loss(sh, numerics, unflat(as_f32(q), sh.layers), row)
    )(p)
    return loss, {n: gsum[n] + g[n] for n in gsum}


def _adamw(opt, p, mu, nu, gsum, factor, lr, step):
    """AdamW with decoupled decay on matrices, on the gradient ``gsum *
    factor``; parameters stored in bfloat16."""
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    step = jnp.asarray(step, jnp.float32)
    decay = {n: opt["weight_decay"] * (n.split(".")[-1] in MATRICES + ("tok", "unembed"))
             for n in p}
    new_p, new_mu, new_nu = {}, {}, {}
    for n in p:
        g = gsum[n] * factor
        w = p[n].astype(jnp.float32)
        m = b1 * mu[n] + (1 - b1) * g
        v = b2 * nu[n] + (1 - b2) * g * g
        u = (m / (1 - b1 ** step)) / (jnp.sqrt(v / (1 - b2 ** step)) + eps)
        new_p[n] = (w - lr * (u + decay[n] * w)).astype(jnp.bfloat16)
        new_mu[n], new_nu[n] = m, v
    return new_p, new_mu, new_nu


def unflat(f: dict, n_layers: int) -> dict:
    """Inverse of :func:`flat`."""
    out = {k: f[k] for k in ("tok", "unembed", "final_norm")}
    out["layers"] = [{n.split(".", 1)[1]: a for n, a in f.items()
                      if n.startswith(f"{l}.")} for l in range(n_layers)]
    return out


def worst_leaf_gap(got: dict, want: dict, skip=()) -> float:
    """Largest ``|got - want|`` over the leaves, each against the larger of
    its own ``want`` and the median ``want`` of all leaves."""
    med = float(np.median(list(want.values())))
    gaps = [abs(got[n] - want[n]) / max(want[n], med, 1e-30)
            for n in want if n not in skip]
    return max(gaps) if gaps else float("nan")


@lru_cache(maxsize=None)
def _layer_fn(sh: Shapes, numerics: str):
    return jax.jit(partial(layer, sh, numerics))


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
    xs = [head["tok"][jnp.asarray(seq[:-1])] for seq in sequences]
    step = _layer_fn(sh, numerics)
    for l in range(sh.layers):
        w = layer_weights(l)
        xs = [step(w, x) for x in xs]
        del w
    out = _logits_fn(sh, numerics)
    return [out(head, x)[served_from - 1:] for x in xs]


def widest_gap(rows, chosen) -> float:
    """Widest gap by which the logit of the token ``chosen`` at each row
    lies below that row's best logit, in units of the row's standard
    deviation over the vocabulary (so that models of other logit scales
    read alike)."""
    chosen = jnp.asarray(chosen)
    best = jnp.max(rows, axis=-1)
    got = jnp.take_along_axis(rows, chosen[:, None], axis=-1)[:, 0]
    return float(jnp.max((best - got) / jnp.std(rows, axis=-1)))
