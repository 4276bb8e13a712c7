"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpreted
on the CPU), and the backend helper that picks the kernel mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # tier-1 container lacks hypothesis: deterministic shim
    from _hypothesis_shim import given, settings, strategies as st

from repro.kernels import nm_prune as nmk
from repro.kernels import ops, quant8, ref
from repro.kernels.backend import interpret_for, resolve


# ---------------------------------------------------------------------------
# backend helper: one decision for every kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False)])
def test_interpret_for_known_backends(backend, interpret):
    assert interpret_for(backend) is interpret


@pytest.mark.parametrize("backend", ["gpu", "cuda", "rocm", "metal"])
def test_interpret_for_unknown_backend_raises(backend):
    with pytest.raises(RuntimeError, match=backend):
        interpret_for(backend)


def test_resolve_default_follows_backend_and_explicit_wins():
    assert resolve(None) is interpret_for(jax.default_backend())
    assert resolve(True) is True and resolve(False) is False


# ---------------------------------------------------------------------------
# quant8
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(7,), (100, 33), (3, 5, 17), (4096,)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bits", [4, 8])
def test_quant_shapes_dtypes(shape, dtype, bits):
    key = jax.random.PRNGKey(42)
    x = (jax.random.normal(key, shape) * 5).astype(dtype)
    out = ops.quantize_dequantize(x, key, bits=bits)
    assert out.shape == x.shape and out.dtype == x.dtype
    s = 2 ** (bits - 1) - 1
    flat = np.asarray(x, np.float32).reshape(-1)
    err = np.abs(np.asarray(out, np.float32).reshape(-1) - flat)
    # error bounded by the global max scale plus the output dtype's own
    # round-off of the dequantized value (bf16: eps = 2^-7)
    dtype_eps = np.finfo(np.float32).eps if dtype == jnp.float32 else 2.0**-7
    amax = np.abs(flat).max()
    assert err.max() <= amax / s + amax * dtype_eps + 1e-2


def test_quant_kernel_vs_oracle_exact():
    key = jax.random.PRNGKey(0)
    rows = quant8.TILE_ROWS * 3
    x = jax.random.normal(key, (rows, quant8.QBLOCK)) * 7
    noise = jax.random.uniform(jax.random.PRNGKey(1), x.shape)
    out = quant8.quant_dequant_2d(x, noise, bits=8)
    exp = ref.quant_dequant_ref(x, noise, bits=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-6)


def test_quant_zero_block_safe():
    x = jnp.zeros((quant8.TILE_ROWS, quant8.QBLOCK))
    noise = jnp.full(x.shape, 0.99)
    out = quant8.quant_dequant_2d(x, noise)
    assert bool(jnp.all(jnp.isfinite(out)))


# ---------------------------------------------------------------------------
# nm_prune
# ---------------------------------------------------------------------------
@settings(max_examples=12, deadline=None)
@given(n=st.sampled_from([1, 2, 3]), m=st.sampled_from([4, 8]),
       seed=st.integers(0, 2**16))
def test_nm_kernel_vs_oracle(n, m, seed):
    key = jax.random.PRNGKey(seed)
    w = jax.random.normal(key, (nmk.TILE_R, nmk.TILE_C))
    s = jnp.abs(w)
    out, mask = nmk.nm_prune_2d(w, s, n=n, m=m)
    eo, em = ref.nm_prune_ref(w, s, n=n, m=m)
    np.testing.assert_allclose(np.asarray(mask), np.asarray(em))
    np.testing.assert_allclose(np.asarray(out), np.asarray(eo))


def test_nm_with_ties():
    """Tie-breaking must keep exactly n per group even with equal scores."""
    w = jnp.ones((nmk.TILE_R, nmk.TILE_C))
    s = jnp.ones_like(w)
    _, mask = nmk.nm_prune_2d(w, s, n=2, m=4)
    grp = np.asarray(mask).reshape(-1, 4, nmk.TILE_C)
    assert (grp.sum(1) == 2).all()


@pytest.mark.parametrize("shape", [(132, 70), (256, 256), (300, 129)])
def test_nm_ops_padding(shape):
    key = jax.random.PRNGKey(1)
    w = jax.random.normal(key, shape)
    out, mask = ops.prune_nm(w, jnp.abs(w), 2, 4)
    assert out.shape == shape
    # interior groups are exactly 2:4 (shape[0] may not divide 4 at the tail)
    r4 = (shape[0] // 4) * 4
    grp = np.asarray(mask)[:r4].reshape(-1, 4, shape[1])
    assert (grp.sum(1) == 2).all()


# ---------------------------------------------------------------------------
# wanda_score fused kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["wanda", "ria", "symwanda"])
@pytest.mark.parametrize("dims", [(256, 128), (384, 256)])
def test_wanda_kernel_modes(mode, dims):
    d_in, d_out = dims
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    W = jax.random.normal(k1, (d_in, d_out)) * 0.2
    X = jax.random.normal(k2, (64, d_in)) * jnp.exp(
        jax.random.normal(jax.random.PRNGKey(9), (d_in,)))
    out, mask = ops.prune_scored(W, X, mode=mode, sparsity=0.5)
    assert out.shape == W.shape
    kept = float(mask.mean())
    assert abs(kept - 0.5) < 0.02
    np.testing.assert_allclose(np.asarray(out), np.asarray(W * mask), rtol=1e-6)
