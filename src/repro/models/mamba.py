"""Mamba2 SSD (state-space duality) block. [arXiv:2405.21060]

TPU adaptation: the SSD algorithm is already matmul-dominated (the paper's
point), so it maps naturally onto the MXU.  Training/prefill uses the chunked
formulation: quadratic attention-like term inside chunks of length Q plus an
inter-chunk state recurrence handled with ``jax.lax.associative_scan`` (log-
depth, shardable).  Decode is the O(1) recurrent step on a (B, H, hd, N)
state.

Parameterization follows the reference: in_proj -> [z, x, B, C, dt], causal
depthwise conv over (x,B,C), A scalar-per-head (negative via -exp(a_log)),
per-head dt bias, D skip, gated RMSNorm before out_proj.

The conv, the chunked SSD and the decode's state step are named scopes
(``mamba/conv``, ``mamba/ssd``, ``mamba/state_step``) in each HLO
instruction's metadata.  On a TPU the decode's state step is one Pallas
kernel that reads and writes each layer's state once, in the stack it
lies in (``kernels/ssm_step.py``); ``fused_state_steps`` counts the layers
it serves while a decode traces.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import backend as kernel_backend
from repro.models.layers import _dense_init, init_rmsnorm, rmsnorm
from repro.obs.trace import annotate


def mamba_dims(d_model: int, cfg) -> dict:
    d_inner = cfg.expand * d_model
    n_heads = d_inner // cfg.head_dim
    conv_dim = d_inner + 2 * cfg.n_groups * cfg.d_state
    in_dim = 2 * d_inner + 2 * cfg.n_groups * cfg.d_state + n_heads
    return dict(d_inner=d_inner, n_heads=n_heads, conv_dim=conv_dim, in_dim=in_dim)


def init_mamba(key, d_model: int, cfg, dtype) -> dict:
    dims = mamba_dims(d_model, cfg)
    ks = jax.random.split(key, 4)
    H = dims["n_heads"]
    return {
        "in_proj": _dense_init(ks[0], (d_model, dims["in_dim"]), dtype),
        "conv_w": _dense_init(ks[1], (cfg.d_conv, dims["conv_dim"]), dtype, scale=0.5),
        "conv_b": jnp.zeros((dims["conv_dim"],), dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "dt_bias": jnp.full((H,), -2.0, jnp.float32),  # softplus^-1(~0.12)
        "D": jnp.ones((H,), jnp.float32),
        "norm": init_rmsnorm(dims["d_inner"], dtype),
        "out_proj": _dense_init(ks[2], (dims["d_inner"], d_model), dtype),
    }


def _split_proj(params, u, cfg, dims):
    """u (B,S,d_model) -> z,(conv inputs x,B,C),dt."""
    zxbcdt = u @ params["in_proj"]
    di, G, N, H = dims["d_inner"], cfg.n_groups, cfg.d_state, dims["n_heads"]
    z, xBC, dt = jnp.split(zxbcdt, [di, di + dims["conv_dim"]], axis=-1)
    return z, xBC, dt


def _causal_conv(params, xBC, cfg):
    """Depthwise causal conv1d along S. xBC (B,S,conv_dim)."""
    K = cfg.d_conv
    pad = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(pad[:, i : i + xBC.shape[1], :] * params["conv_w"][i] for i in range(K))
    return jax.nn.silu(out + params["conv_b"])


def _ssd_chunked(x, dt, A, B_, C_, D, chunk: int):
    """SSD chunked scan.
    x (B,S,H,hd); dt (B,S,H) (post-softplus); A (H,) negative;
    B_,C_ (B,S,G,N); D (H,). Returns y (B,S,H,hd) and final state (B,H,hd,N).
    """
    Bsz, S, H, hd = x.shape
    G, N = B_.shape[2], B_.shape[3]
    nch = S // chunk
    rep = H // G

    xc = x.reshape(Bsz, nch, chunk, H, hd).astype(jnp.float32)
    dtc = dt.reshape(Bsz, nch, chunk, H).astype(jnp.float32)
    Bc = B_.reshape(Bsz, nch, chunk, G, N).astype(jnp.float32)
    Cc = C_.reshape(Bsz, nch, chunk, G, N).astype(jnp.float32)

    dA = dtc * A[None, None, None, :]                 # (B,K,Q,H), negative
    cs = jnp.cumsum(dA, axis=2)                       # cumulative log-decay
    seg = cs[:, :, :, None, :] - cs[:, :, None, :, :]  # (B,K,Q,Q,H) log decay i<-j
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    L = jnp.where(causal[None, None, :, :, None], jnp.exp(seg), 0.0)

    # intra-chunk (diagonal) term: per group then broadcast to heads
    CB = jnp.einsum("bkqgn,bkpgn->bkqpg", Cc, Bc)     # (B,K,Q,Q,G)
    CB = jnp.repeat(CB, rep, axis=-1)                 # (B,K,Q,Q,H)
    M = CB * L * dtc[:, :, None, :, :]                # weight for source pos p
    y_diag = jnp.einsum("bkqph,bkphd->bkqhd", M, xc)

    # chunk states: sum_p decay(end<-p) * dt_p * x_p outer B_p
    decay_end = jnp.exp(cs[:, :, -1:, :] - cs)        # (B,K,Q,H)
    w = decay_end * dtc                               # (B,K,Q,H)
    Brep = jnp.repeat(Bc, rep, axis=3)                # (B,K,Q,H,N)
    states = jnp.einsum("bkqh,bkqhd,bkqhn->bkhdn", w, xc, Brep)

    # inter-chunk recurrence: S_k = exp(sum dA_k) * S_{k-1} + states_k
    chunk_decay = jnp.exp(cs[:, :, -1, :])            # (B,K,H)

    def combine(a, b):
        d1, s1 = a
        d2, s2 = b
        return d1 * d2, s2 + d2[..., None, None] * s1

    dec, st = jax.lax.associative_scan(
        combine, (chunk_decay, states), axis=1)
    # state entering chunk k is st[k-1]
    init = jnp.zeros_like(st[:, :1])
    st_prev = jnp.concatenate([init, st[:, :-1]], axis=1)  # (B,K,H,hd,N)

    # off-diagonal term: y_q += C_q . (decay(q<-start) * S_prev)
    decay_in = jnp.exp(cs)                            # (B,K,Q,H)
    Crep = jnp.repeat(Cc, rep, axis=3)                # (B,K,Q,H,N)
    y_off = jnp.einsum("bkqhn,bkhdn,bkqh->bkqhd", Crep, st_prev, decay_in)

    y = (y_diag + y_off).reshape(Bsz, S, H, hd)
    y = y + x.astype(jnp.float32) * D[None, None, :, None]
    final_state = st[:, -1]                           # (B,H,hd,N)
    return y, final_state


def mamba_train(params, u, cfg, d_model: int) -> jax.Array:
    y, _ = mamba_forward(params, u, cfg, d_model)
    return y


def mamba_forward(params, u, cfg, d_model: int, return_cache: bool = False):
    dims = mamba_dims(d_model, cfg)
    di, H, G, N = dims["d_inner"], dims["n_heads"], cfg.n_groups, cfg.d_state
    hd = cfg.head_dim
    Bsz, S, _ = u.shape

    from repro.sharding.context import constrain_named

    z, xBC_raw, dt = _split_proj(params, u, cfg, dims)
    with annotate("mamba/conv"):
        xBC = _causal_conv(params, xBC_raw, cfg)
    x, B_, C_ = jnp.split(xBC, [di, di + G * N], axis=-1)
    # optional SSD head sharding (perf variant): keeps the (B,K,Q,Q,H)
    # intra-chunk tensors model-sharded over heads instead of replicated
    x = constrain_named("ssd_x", x.reshape(Bsz, S, H, hd))
    B_ = B_.reshape(Bsz, S, G, N)
    C_ = C_.reshape(Bsz, S, G, N)
    dt = constrain_named("ssd_dt",
                         jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"]))
    A = -jnp.exp(params["a_log"])

    chunk = min(cfg.chunk_size, S)
    if S % chunk:  # pad to a chunk multiple (masked tail contributes ~0 via dt)
        padlen = chunk - S % chunk
        x = jnp.pad(x, ((0, 0), (0, padlen), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, padlen), (0, 0)))
        B_ = jnp.pad(B_, ((0, 0), (0, padlen), (0, 0), (0, 0)))
        C_ = jnp.pad(C_, ((0, 0), (0, padlen), (0, 0), (0, 0)))
    with annotate("mamba/ssd"):
        y, state = _ssd_chunked(x, dt, A, B_, C_, params["D"], chunk)
    y = y[:, :S]

    y = y.reshape(Bsz, S, di).astype(u.dtype)
    y = rmsnorm(params["norm"], y * jax.nn.silu(z))
    out = y @ params["out_proj"]
    if return_cache:
        # decode-compatible cache: final SSM state + last (d_conv-1) raw conv inputs
        K = cfg.d_conv
        tail = xBC_raw[:, -(K - 1):, :]
        if S < K - 1:
            tail = jnp.pad(xBC_raw, ((0, 0), (K - 1 - S, 0), (0, 0)))
        return out, {"ssm": state, "conv": tail}
    return out, state


def mamba_cache_spec(d_model: int, cfg, batch: int, dtype):
    dims = mamba_dims(d_model, cfg)
    return {
        "ssm": jax.ShapeDtypeStruct(
            (batch, dims["n_heads"], cfg.head_dim, cfg.d_state), jnp.float32),
        "conv": jax.ShapeDtypeStruct((batch, cfg.d_conv - 1, dims["conv_dim"]), dtype),
    }


def mamba_decode(params, u, caches: dict, layer, cfg, d_model: int):
    """One-token step of layer ``layer`` of this position's stacked caches
    {ssm (P,B,H,hd,N), conv (P,B,K-1,conv_dim)}; u (B,1,d_model).  Returns
    (out, caches) with that layer's state and conv tail replaced in place."""
    dims = mamba_dims(d_model, cfg)
    di, H, G, N = dims["d_inner"], dims["n_heads"], cfg.n_groups, cfg.d_state
    hd = cfg.head_dim
    Bsz = u.shape[0]

    z, xBC, dt = _split_proj(params, u, cfg, dims)     # (B,1,*)
    with annotate("mamba/conv"):
        conv = jax.lax.dynamic_index_in_dim(caches["conv"], layer, 0,
                                            keepdims=False)
        conv_in = jnp.concatenate([conv, xBC], axis=1)  # (B,K,conv_dim)
        w = params["conv_w"]                            # (K, conv_dim)
        conv_out = jnp.sum(conv_in * w[None], axis=1, keepdims=True) + params["conv_b"]
        xBC_t = jax.nn.silu(conv_out)                   # (B,1,conv_dim)
        new_conv = jax.lax.dynamic_update_index_in_dim(
            caches["conv"], conv_in[:, 1:], layer, 0)

    x, B_, C_ = jnp.split(xBC_t[:, 0], [di, di + G * N], axis=-1)
    x = x.reshape(Bsz, H, hd).astype(jnp.float32)
    B_ = B_.reshape(Bsz, G, N).astype(jnp.float32)
    C_ = C_.reshape(Bsz, G, N).astype(jnp.float32)
    dt_t = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + params["dt_bias"])  # (B,H)
    A = -jnp.exp(params["a_log"])
    da = jnp.exp(dt_t * A[None])                        # (B,H)

    step = _kernel_state_step if _fuses_state_step(hd, N) else _state_step
    with annotate("mamba/state_step"):
        state, y = step(caches["ssm"], layer, da, dt_t, x, B_, C_)
        y = y + x * params["D"][None, :, None]

    y = y.reshape(Bsz, 1, di).astype(u.dtype)
    y = rmsnorm(params["norm"], y * jax.nn.silu(z))
    out = y @ params["out_proj"]
    return out, {"ssm": state, "conv": new_conv}


def _state_step(ssm, layer, da, dt, x, B_, C_):
    """Layer ``layer`` of the stacked state (P,B,H,hd,N) steps in
    ``jax.numpy``: h' = da h + dt x B, y = C h'.  da, dt (B,H); x (B,H,hd);
    B_, C_ (B,G,N).  Returns (the stack, y (B,H,hd) without the D skip)."""
    rep = ssm.shape[2] // B_.shape[1]
    Brep = jnp.repeat(B_, rep, axis=1)                  # (B,H,N)
    Crep = jnp.repeat(C_, rep, axis=1)
    state = jax.lax.dynamic_index_in_dim(ssm, layer, 0, keepdims=False)
    state = state * da[..., None, None] + jnp.einsum(
        "bh,bhd,bhn->bhdn", dt, x, Brep)
    y = jnp.einsum("bhdn,bhn->bhd", state, Crep)
    return jax.lax.dynamic_update_index_in_dim(ssm, state, layer, 0), y


def _fuses_state_step(hd: int, n: int) -> bool:
    """Whether the decode's state step runs through the Pallas kernel
    (``kernels/ssm_step.py``): where kernels compile (a TPU), at widths that
    tile it, and with no sharding in force, since GSPMD cannot partition a
    Mosaic kernel.  Elsewhere the ``jax.numpy`` step runs."""
    from repro.sharding.context import named_specs_installed

    return (not kernel_backend.interpret_for() and hd % 8 == 0 and n % 128 == 0
            and not named_specs_installed()
            and jax.sharding.get_abstract_mesh().empty)


# the tallies ``fused_state_steps`` has open in this context
_TALLIES: contextvars.ContextVar = contextvars.ContextVar(
    "fused_state_step_tallies", default=())


@contextlib.contextmanager
def fused_state_steps():
    """While a decode traces, count the Mamba layers whose state step runs
    through the kernel: yields a list whose entries' first items sum to it
    (one entry a traced step, worth its stack's periods)."""
    entries: list = []
    token = _TALLIES.set(_TALLIES.get() + (entries,))
    try:
        yield entries
    finally:
        _TALLIES.reset(token)


def _kernel_state_step(ssm, layer, da, dt, x, B_, C_):
    """``_state_step`` through the kernel, in place.  The kernel steps one
    decode's stack: a decode mapped by ``jax.vmap`` (``serve/engine.py``)
    takes the ``jax.numpy`` step instead, and the tally forgets its
    layers."""
    from repro.kernels import ops as kernel_ops

    layers = [ssm.shape[0]]             # this position's layer of each period
    for entries in _TALLIES.get():
        entries.append(layers)

    @jax.custom_batching.custom_vmap
    def step(*args):
        return kernel_ops.ssm_state_step(*args)

    @step.def_vmap
    def _batched(axis_size, in_batched, *args):
        layers[0] = 0
        axes = [0 if b else None for b in in_batched]
        return jax.vmap(_state_step, in_axes=axes)(*args), (True, True)

    return step(ssm, layer, da, dt, x, B_, C_)
