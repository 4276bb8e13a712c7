"""Unit-level model tests: attention variants, SSD math, MoE dispatch, RoPE."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # tier-1 container lacks hypothesis: deterministic shim
    from _hypothesis_shim import given, settings, strategies as st

from repro.models import attention as attn
from repro.models import mamba as mamba_lib
from repro.models import moe as moe_lib
from repro.models.layers import apply_rope, cross_entropy_loss, rmsnorm, init_rmsnorm
from repro.configs.base import MambaConfig


# ---------------------------------------------------------------------------
# flash attention == naive attention (all mask kinds)
# ---------------------------------------------------------------------------
def _naive_attention(q, k, v, kind, window, chunk):
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qf = q.astype(jnp.float32).reshape(B, S, KV, G, hd) / math.sqrt(hd)
    s = jnp.einsum("bqkgh,bnkh->bqkgn", qf, k.astype(jnp.float32))
    qi, ki = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    ok = qi >= ki
    if kind == "attn_swa":
        ok &= (qi - ki) < window
    if kind == "attn_chunk":
        ok &= (qi // chunk) == (ki // chunk)
    s = jnp.where(ok[None, :, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqkgn,bnkh->bqkgh", p, v.astype(jnp.float32))
    return out.reshape(B, S, H, hd)


@pytest.mark.parametrize("kind,window,chunk,S", [
    ("attn", 0, 0, 24), ("attn_swa", 8, 0, 24), ("attn_chunk", 0, 16, 24),
    ("attn", 0, 0, 64), ("attn_swa", 8, 0, 64), ("attn_chunk", 0, 16, 64),
    # windows and chunks several 16-wide KV blocks long, or not a multiple
    # of the block, and a sequence that ends inside a chunk
    ("attn_swa", 24, 0, 96), ("attn_chunk", 0, 32, 96),
    ("attn_swa", 40, 0, 64), ("attn_chunk", 0, 16, 40)])
def test_flash_matches_naive(kind, window, chunk, S):
    B, H, KV, hd = 2, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    out = attn._flash_attention(q, k, v, kind, window, chunk, block_q=16, block_k=16)
    exp = _naive_attention(q, k, v, kind, window, chunk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5)


def test_flash_irregular_sizes():
    """Padding path: S not divisible by blocks."""
    B, S, H, KV, hd = 1, 37, 2, 1, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    out = attn._flash_attention(q, k, v, "attn", 0, 0, block_q=16, block_k=16)
    exp = _naive_attention(q, k, v, "attn", 0, 0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5)


# ---------------------------------------------------------------------------
# SSD chunked scan == naive recurrence
# ---------------------------------------------------------------------------
@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**10), chunk=st.sampled_from([4, 8]))
def test_ssd_matches_recurrence(seed, chunk):
    B, S, H, hd, N = 1, 24, 2, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (B, S, H, hd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    B_ = jax.random.normal(ks[3], (B, S, 1, N))
    C_ = jax.random.normal(ks[4], (B, S, 1, N))
    D = jnp.ones((H,))
    y, state = mamba_lib._ssd_chunked(x, dt, A, B_, C_, D, chunk)

    # naive sequential recurrence
    h = np.zeros((B, H, hd, N))
    xs, dts = np.asarray(x), np.asarray(dt)
    Bs, Cs = np.asarray(B_), np.asarray(C_)
    ys = np.zeros((B, S, H, hd))
    for t in range(S):
        da = np.exp(dts[:, t] * np.asarray(A))            # (B,H)
        h = h * da[..., None, None] + np.einsum(
            "bh,bhd,bn->bhdn", dts[:, t], xs[:, t], Bs[:, t, 0])
        ys[:, t] = np.einsum("bhdn,bn->bhd", h, Cs[:, t, 0]) + xs[:, t]
    np.testing.assert_allclose(np.asarray(y), ys, atol=1e-3)
    np.testing.assert_allclose(np.asarray(state), h, atol=1e-3)


# ---------------------------------------------------------------------------
# MoE dispatch properties
# ---------------------------------------------------------------------------
def test_moe_no_drop_routes_everything():
    E, K, d = 4, 2, 32
    params = moe_lib.init_moe(jax.random.PRNGKey(0), d, 64, E, True, False, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, d))
    y, aux = moe_lib.moe_ffn(params, x, num_experts=E, top_k=K, capacity_factor=1.0,
                             act="silu", gated=True, shared_expert=False, no_drop=True)
    assert y.shape == x.shape
    assert float(aux) >= 1.0 - 1e-5  # Switch aux loss lower bound is 1 at balance


def test_moe_capacity_drops_tokens():
    """With tiny capacity, outputs must differ from the no-drop result."""
    E, K, d = 4, 1, 16
    params = moe_lib.init_moe(jax.random.PRNGKey(0), d, 32, E, True, False, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, d))
    kw = dict(num_experts=E, top_k=K, act="silu", gated=True, shared_expert=False)
    y_full, _ = moe_lib.moe_ffn(params, x, capacity_factor=4.0, **kw)
    y_tight, _ = moe_lib.moe_ffn(params, x, capacity_factor=0.25, **kw)
    assert float(jnp.max(jnp.abs(y_full - y_tight))) > 1e-6


def test_moe_matches_dense_expert_sum():
    """no_drop top-E routing == weighted sum over all experts computed densely."""
    E, d, ff = 3, 16, 24
    params = moe_lib.init_moe(jax.random.PRNGKey(2), d, ff, E, True, False, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 5, d))
    y, _ = moe_lib.moe_ffn(params, x, num_experts=E, top_k=E, capacity_factor=1.0,
                           act="silu", gated=True, shared_expert=False, no_drop=True)
    xt = x.reshape(-1, d)
    logits = xt @ params["router"]
    w = jax.nn.softmax(logits, -1)
    dense = jnp.zeros_like(xt)
    for e in range(E):
        h = xt @ params["w_in"][e]
        g = xt @ params["w_gate"][e]
        dense += w[:, e:e + 1] * ((jax.nn.silu(g) * h) @ params["w_out"][e])
    np.testing.assert_allclose(np.asarray(y.reshape(-1, d)), np.asarray(dense),
                               atol=1e-4)


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------
def test_rope_preserves_norm_and_relativity():
    hd = 32
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 1, hd))
    pos = jnp.array([[0, 1, 5, 9]])
    out = apply_rope(q, pos, 10000.0)
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(out, axis=-1)),
                               np.asarray(jnp.linalg.norm(q, axis=-1)), rtol=1e-5)
    # relative property: <R(p)q, R(p+k)v> depends only on k
    v = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, hd))
    def dot_at(p1, p2):
        a = apply_rope(q[:, :1], jnp.array([[p1]]), 10000.0)
        b = apply_rope(v, jnp.array([[p2]]), 10000.0)
        return float(jnp.sum(a * b))
    assert abs(dot_at(3, 7) - dot_at(10, 14)) < 1e-3


def test_rmsnorm_scale():
    p = init_rmsnorm(8, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 8)) * 100
    y = rmsnorm(p, x)
    rms = jnp.sqrt(jnp.mean(y**2, -1))
    np.testing.assert_allclose(np.asarray(rms), 1.0, rtol=1e-4)


def test_cross_entropy_matches_manual():
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 5))
    targets = jnp.array([[0, 1, 2], [3, 4, 0]])
    got = float(cross_entropy_loss(logits, targets))
    p = jax.nn.log_softmax(logits, -1)
    exp = -float(jnp.mean(jnp.take_along_axis(p, targets[..., None], -1)))
    assert abs(got - exp) < 1e-5
