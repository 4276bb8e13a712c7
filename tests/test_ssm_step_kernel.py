"""The Mamba-2 decode state-step kernel (``kernels/ssm_step.py``), in the
Pallas interpreter, against the model's own ``jax.numpy`` step
(``models/mamba._state_step``): the stepped layer's new state and readout
agree to float32 rounding (the kernel sums the readout over ``N`` in its own
order), and every other layer of the stack is left bit for bit as it was.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ssm_step
from repro.models.mamba import _state_step

RTOL = 1e-5


# (periods, slots, heads, head_dim, d_state, groups, layer, block bytes)
CASES = [
    pytest.param(2, 2, 4, 8, 128, 1, 0, None, id="first-layer"),
    pytest.param(3, 2, 8, 8, 128, 2, 2, None, id="groups2-last-layer"),
    pytest.param(3, 1, 8, 16, 128, 4, 1, None, id="groups4-middle"),
    pytest.param(2, 2, 8, 8, 256, 1, 1, None, id="d_state256"),
    # 4 KB a head: blocks of 2 heads, 2 blocks to a group
    pytest.param(2, 2, 8, 8, 128, 2, 1, 8 * 1024, id="several-blocks-a-group"),
]


def _inputs(periods, slots, heads, hd, n, groups, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    state = jax.random.normal(ks[0], (periods, slots, heads, hd, n), jnp.float32)
    da = jax.random.uniform(ks[1], (slots, heads), jnp.float32, 0.5, 1.0)
    dt = jax.random.uniform(ks[2], (slots, heads), jnp.float32, 0.01, 0.2)
    x = jax.random.normal(ks[3], (slots, heads, hd), jnp.float32)
    b = jax.random.normal(ks[4], (slots, groups, n), jnp.float32)
    c = jax.random.normal(ks[5], (slots, groups, n), jnp.float32)
    return state, da, dt, x, b, c


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize(
    "periods,slots,heads,hd,n,groups,layer,block_bytes", CASES)
def test_state_step_kernel_matches_jnp_step(monkeypatch, periods, slots, heads,
                                            hd, n, groups, layer, block_bytes):
    if block_bytes is not None:
        monkeypatch.setattr(ssm_step, "BLOCK_BYTES", block_bytes)
        assert ssm_step.heads_per_block(heads // groups, hd, n) < heads // groups
    state, da, dt, x, b, c = _inputs(periods, slots, heads, hd, n, groups)
    lid = jnp.int32(layer)
    want_stack, want_y = _state_step(state, lid, da, dt, x, b, c)
    got_stack, got_y = ops.ssm_state_step(state, lid, da, dt, x, b, c,
                                          interpret=True)
    assert got_stack.shape == state.shape and got_y.shape == want_y.shape
    assert _rel(got_stack[layer], want_stack[layer]) <= RTOL
    assert _rel(got_y, want_y) <= RTOL
    others = [i for i in range(periods) if i != layer]
    np.testing.assert_array_equal(np.asarray(got_stack)[others],
                                  np.asarray(state)[others])
