"""The plain reference against the program at a tiny size on the CPU: the
same initial weights, the same parameter layout, the same forward pass."""
from __future__ import annotations

from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip.reference import dense_lm
from benchmarks.chip.traffic import program

from conftest import TINY


def program_flat(tree) -> dict:
    """The program's parameters under the reference's leaf names."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name, stacked = program._leaf_name(path)
        if stacked:
            for layer in range(x.shape[0]):
                out[f"{layer}.{name}"] = np.asarray(x[layer], np.float32)
        else:
            out[name] = np.asarray(x, np.float32)
    return out


def reference_params(shapes, tree) -> dict:
    return dict(dense_lm.as_f32(dense_lm.head_of(tree)),
                layers=[dense_lm.as_f32(dense_lm.layer_of(tree, l))
                        for l in range(shapes.layers)])


@pytest.fixture(scope="module")
def shapes():
    return dense_lm.Shapes.from_config(TINY)


def test_init_matches_train(shapes):
    from repro.models import init_params

    seed_key = jax.random.PRNGKey(1234)
    _, kinit = jax.random.split(seed_key)        # as train() splits it
    cfg = program.model_config(TINY)
    prog = program_flat(jax.jit(partial(init_params, cfg=cfg))(kinit))
    ref = {n: np.asarray(a, np.float32) for n, a in
           dense_lm.flat(dense_lm.init_like_program(shapes, seed_key)).items()}
    assert sorted(prog) == sorted(ref)
    # drawn alike; the program draws inside one jitted, vmapped init, where
    # the compiler may round a product differently: a rare value then sits
    # one bfloat16 step away
    for n in ref:
        off = prog[n] != ref[n]
        assert off.mean() <= 1e-3, n
        step = np.abs(ref[n][off]) * 2.0 ** -7
        assert np.all(np.abs(prog[n] - ref[n])[off] <= step * 1.01), n


def test_serving_weights_have_the_program_layout(shapes):
    from repro.models import init_params

    cfg = program.model_config(TINY)
    want = jax.eval_shape(partial(init_params, cfg=cfg), jax.random.PRNGKey(0))
    got = jax.eval_shape(partial(dense_lm.bench_program_tree, shapes),
                         jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    tree = jax.jit(partial(dense_lm.bench_program_tree, shapes))(
        jax.random.PRNGKey(5))
    flat = program_flat(tree)
    for name, a in dense_lm.layer_of(tree, 1).items():
        np.testing.assert_array_equal(np.asarray(a, np.float32), flat[f"1.{name}"])


def test_forward_matches_program(shapes):
    """Logits of the program's training forward, run in float32, against
    the reference's: GQA, the window (24 < 32 positions), QKV bias, the
    gated MLP, norms with non-unit scales."""
    from repro.models import forward_train

    cfg = replace(program.model_config(TINY), dtype="float32")
    tree = jax.jit(partial(dense_lm.bench_program_tree, shapes))(
        jax.random.PRNGKey(3))
    params = dense_lm.as_f32(tree)
    tokens = np.random.default_rng(0).integers(0, TINY["vocab_size"], (2, 32))
    with jax.default_matmul_precision("highest"):
        got, _ = forward_train(params, cfg, {"tokens": jnp.asarray(tokens)},
                               remat="none")
        want = np.stack([np.asarray(dense_lm.forward(
            shapes, "f32", reference_params(shapes, tree), jnp.asarray(row)))
            for row in tokens])
    scale = np.abs(want).max()
    assert np.abs(np.asarray(got) - want).max() <= 1e-5 * scale


def test_control_differs_from_reference(shapes):
    """The fp8 control computes the same forward with coarser products."""
    tree = jax.jit(partial(dense_lm.bench_program_tree, shapes))(
        jax.random.PRNGKey(3))
    params = reference_params(shapes, tree)
    row = jnp.arange(32) % TINY["vocab_size"]
    ref = np.asarray(dense_lm.forward(shapes, "f32", params, row))
    ctl = np.asarray(dense_lm.forward(shapes, "fp8", params, row))
    err = np.abs(ctl - ref).max() / np.abs(ref).max()
    assert 1e-3 < err < 0.5
