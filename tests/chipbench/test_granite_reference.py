"""The hybrid reference's serving weights have the program's layout, at
the published sizes (shapes only) and at a tiny size (values)."""
from __future__ import annotations

from functools import partial

import jax
import numpy as np
import pytest

from benchmarks.chip import hybrid_flops
from benchmarks.chip.reference import granite_hybrid
from benchmarks.chip.traffic.serve_batch_hybrid import model_config

from hybrid_tiny import published, tiny_hybrid


@pytest.mark.parametrize("make", [published, tiny_hybrid],
                         ids=["published", "tiny"])
def test_serving_weights_have_the_program_layout(make):
    from repro.models import init_params

    c = make()
    sh = granite_hybrid.Shapes.from_config(c)
    want = jax.eval_shape(partial(init_params, cfg=model_config(c)),
                          jax.random.PRNGKey(0))
    got = jax.eval_shape(partial(granite_hybrid.bench_program_tree, sh),
                         jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    stored = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(got))
    assert stored == hybrid_flops.weight_bytes(c)


def test_published_period_and_widths():
    sh = granite_hybrid.Shapes.from_config(published())
    assert sh.period == 10 and sh.layers == 40
    assert sh.layer_types[:10].index("attention") == 5
    assert (sh.head_dim, sh.d_inner, sh.conv_dim) == (64, 4096, 4352)
    cfg = model_config(published())
    assert cfg.nope and cfg.attention_multiplier == 1 / 64
    assert cfg.mamba.expand == 2 and cfg.param_count() == 3_191_237_120


def test_layer_of_reads_each_layer_back():
    """``layer_of`` takes layer ``l`` out of the stacked tree: the tensors
    ``bench_layer`` draws for that layer."""
    sh = granite_hybrid.Shapes.from_config(tiny_hybrid())
    key = jax.random.PRNGKey(5)
    tree = jax.jit(partial(granite_hybrid.bench_program_tree, sh))(key)
    for l in (3, 5):
        got = granite_hybrid.layer_of(sh, tree, l)
        want = granite_hybrid.bench_layer(sh, key, l, sh.layer_types[l])
        assert sorted(got) == sorted(want)
        # drawn alike; the vmapped program may round a float32 product
        # (A_log, dt_bias, D) one step differently
        for name in want:
            np.testing.assert_allclose(np.asarray(got[name], np.float32),
                                       np.asarray(want[name], np.float32),
                                       rtol=3e-7, atol=0)
