"""granite-4.0-h (Mamba-2 layers beside NoPE attention) against its plain
reference, on the CPU at a reduced size that keeps one whole period of the
layer pattern: 10 layers with attention at position 5, d_model 64, 4 query
and 2 KV heads of 16, SSD heads of 16 with d_state 16 and chunk 8, vocab
512, and every multiplier at its published value.

The program runs in float32 here, so the only differences from the
reference are the order of sums: the chunked SSD against the sequential
recurrence, the tiled softmax against the plain one.  Those read 1.5e-6
to 2.4e-6 of the largest logit (3 seeds of weights); ``TOL`` lies 12 times
above.  Holding the SSM state in bfloat16 between decode steps (8 bits of
mantissa) reads 2.1e-3 to 3.6e-3, more than 70 times ``TOL``.
"""
import json
import os
import sys
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, REPO)

from benchmarks.chip.reference import granite_hybrid  # noqa: E402
from benchmarks.chip.traffic.serve_batch_hybrid import model_config  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import (decode_step, forward_train, init_params,  # noqa: E402
                          loss_fn, prefill)
from repro.training.serving import ContinuousBatcher, Request  # noqa: E402

# largest |program - reference| logit over the largest |reference| logit
TOL = 3e-5
DECODE_STEPS = 12


def tiny_config() -> dict:
    with open(os.path.join(REPO, "benchmarks", "chip", "configs",
                           "granite-4.0-h-micro-serve.json")) as f:
        c = json.load(f)
    return dict(c, name="granite-tiny", hidden_size=64,
                shared_intermediate_size=128, intermediate_size=128,
                num_attention_heads=4, num_key_value_heads=2,
                num_hidden_layers=10, layer_types=c["layer_types"][:10],
                mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
                mamba_chunk_size=8, vocab_size=512)


@pytest.fixture(scope="module")
def model():
    c = tiny_config()
    sh = granite_hybrid.Shapes.from_config(c)
    cfg = replace(model_config(c), dtype="float32")
    tree = jax.jit(partial(granite_hybrid.bench_program_tree, sh))(
        jax.random.PRNGKey(7))
    params = granite_hybrid.as_f32(tree)
    ref = dict(granite_hybrid.head_of(params),
               layers=[granite_hybrid.layer_of(sh, params, l)
                       for l in range(sh.layers)])
    return sh, cfg, params, ref


def reference_logits(sh, ref, tokens, state_dtype=jnp.float32):
    with jax.default_matmul_precision("highest"):
        return np.stack([np.asarray(granite_hybrid.forward(
            sh, "f32", ref, jnp.asarray(row), state_dtype)) for row in tokens])


def served_logits(cfg, params, tokens, prompt_len, hold_state=None):
    """Prefill's last logits, then each decode step's, fed the given
    tokens; ``hold_state`` is applied to every SSM state between steps."""
    lg, cache = prefill(params, cfg, {"tokens": tokens[:, :prompt_len]},
                        cache_len=tokens.shape[1] + 1)
    out = [lg[:, -1]]
    step = jax.jit(lambda c, t: decode_step(params, cfg, t, c))
    for t in range(prompt_len, tokens.shape[1]):
        if hold_state is not None:
            cache = jax.tree_util.tree_map_with_path(
                lambda p, a: hold_state(a) if p[-1].key == "ssm" else a, cache)
        lg, cache = step(cache, tokens[:, t:t + 1])
        out.append(lg[:, -1])
    return np.stack([np.asarray(o) for o in out], axis=1)


def gap(got, want) -> float:
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def tokens_for(sh, prompt_len, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, sh.vocab, (2, prompt_len + DECODE_STEPS)),
                       jnp.int32)


@pytest.mark.parametrize("prompt_len", [16, 13], ids=["whole_chunks",
                                                      "part_chunk"])
def test_prefill_and_decode_match_reference(model, prompt_len):
    """Prefill (13 tokens leave a part chunk of 5 for the padded SSD), then
    12 decode steps through the hybrid cache, against the reference's full
    forward over the same tokens."""
    sh, cfg, params, ref = model
    toks = tokens_for(sh, prompt_len)
    want = reference_logits(sh, ref, toks)[:, prompt_len - 1:]
    got = served_logits(cfg, params, toks, prompt_len)
    assert got.shape == want.shape
    assert gap(got, want) <= TOL


def test_train_loss_matches_reference(model):
    sh, cfg, params, ref = model
    rows = tokens_for(sh, 20, seed=1)
    loss, _ = loss_fn(params, cfg, {"tokens": rows[:, :-1],
                                    "targets": rows[:, 1:]}, remat="none")
    with jax.default_matmul_precision("highest"):
        want = np.mean([float(granite_hybrid.sequence_loss(sh, ref, r))
                        for r in rows])
    # a mean of 62 cross-entropies of ~6 nats, each a float32 sum over the
    # vocabulary: the two orders of summation read 0 to 1.9e-7 apart
    assert abs(float(loss) - want) <= 2e-6 * want


def test_state_in_bfloat16_breaks_the_tolerance(model):
    """The same run holding the SSM state in bfloat16 between steps fails
    ``TOL``: the comparison is tight enough to see it."""
    sh, cfg, params, ref = model
    toks = tokens_for(sh, 16)
    want = reference_logits(sh, ref, toks)[:, 15:]
    got = served_logits(cfg, params, toks, 16, hold_state=lambda a: a.astype(
        jnp.bfloat16).astype(jnp.float32))
    assert gap(got, want) > 10 * TOL


def test_default_multipliers_add_nothing():
    """A dense model with the multipliers left at their defaults and one
    with the same values set explicitly give bit-identical logits in
    training, prefill and decode."""
    base = replace(get_config("h2o-danube-1.8b").reduced(), dtype="float32")
    explicit = replace(base, embedding_multiplier=1.0, residual_multiplier=1.0,
                       logits_scaling=1.0, nope=False,
                       attention_multiplier=1.0 / np.sqrt(base.head_dim))
    params = init_params(jax.random.PRNGKey(3), base)
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 12), 0, base.vocab_size)
    outs = []
    for cfg in (base, explicit):
        train, _ = forward_train(params, cfg, {"tokens": toks})
        lg, cache = prefill(params, cfg, {"tokens": toks[:, :11]}, cache_len=16)
        dec, _ = decode_step(params, cfg, toks[:, 11:], cache)
        outs.append([np.asarray(x) for x in (train, lg, dec)])
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)


def test_batcher_refuses_to_pad_the_recurrent_state(model):
    """Requests of one prompt length are served; an admission that would
    left-pad a context of a model with SSM layers is refused before any
    slot changes."""
    sh, cfg, params, _ = model
    cb = ContinuousBatcher(cfg, params, n_slots=2, max_len=32)
    rng = np.random.default_rng(2)
    for rid in range(2):
        cb.submit(Request(rid=rid, prompt=rng.integers(0, sh.vocab, 6).astype(
            np.int32), max_new=3))
    stats = cb.run(max_ticks=20)
    assert stats.completed == 2 and stats.ssm_state_bytes > 0
    cb = ContinuousBatcher(cfg, params, n_slots=2, max_len=32)
    for rid, n in enumerate((6, 5)):
        cb.submit(Request(rid=rid, prompt=np.arange(n, dtype=np.int32)))
    with pytest.raises(ValueError, match="left-pad"):
        cb.step()
    assert len(cb.queue) == 2 and cb.slots == [None, None]


def test_mamba_scopes_name_the_ops(model):
    """The conv, the chunked SSD and the decode's state step carry their
    scopes in the HLO metadata, where a device trace can find them."""
    sh, cfg, params, _ = model
    toks = tokens_for(sh, 16)
    pre = jax.jit(lambda t: prefill(params, cfg, {"tokens": t}, cache_len=20))
    text = pre.lower(toks[:, :16]).as_text(debug_info=True)
    assert "mamba/conv" in text and "mamba/ssd" in text
    cache = jax.eval_shape(pre, toks[:, :16])[1]
    dec = jax.jit(lambda t, c: decode_step(params, cfg, t, c))
    text = dec.lower(toks[:, 16:17], cache).as_text(debug_info=True)
    assert "mamba/conv" in text and "mamba/state_step" in text


def test_donated_cache_serves_the_same_tokens(model):
    """With ``donate_cache`` the decode step writes over the cache it was
    given, which is gone once the step is dispatched, and every request
    gets the tokens the undonated batcher gives it."""
    sh, cfg, params, _ = model
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, sh.vocab, 7).astype(np.int32) for _ in range(4)]
    served = []
    for donate in (False, True):
        cb = ContinuousBatcher(cfg, params, n_slots=2, max_len=32,
                               donate_cache=donate)
        reqs = [Request(rid=rid, prompt=p, max_new=6)
                for rid, p in enumerate(prompts)]
        for r in reqs:
            cb.submit(r)
        cb.step()
        given = jax.tree_util.tree_leaves(cb.cache)
        cb.step()
        assert all(a.is_deleted() for a in given) is donate
        cb.run(max_ticks=40)
        assert cb.stats.completed == 4
        served.append([list(r.generated) for r in reqs])
    assert served[0] == served[1]


@pytest.fixture(scope="module")
def kernel_model():
    """The tiny hybrid at widths the Mamba state-step kernel tiles: 16 SSD
    heads of 8 in 2 groups, d_state 128."""
    c = dict(tiny_config(), mamba_n_heads=16, mamba_d_head=8,
             mamba_d_state=128, mamba_n_groups=2)
    sh = granite_hybrid.Shapes.from_config(c)
    cfg = replace(model_config(c), dtype="float32")
    tree = jax.jit(partial(granite_hybrid.bench_program_tree, sh))(
        jax.random.PRNGKey(11))
    return sh, cfg, granite_hybrid.as_f32(tree)


def test_state_step_kernel_serves_the_decode(kernel_model, monkeypatch):
    """Prefill, then 12 decode steps with each Mamba layer's state step in
    the Pallas kernel (interpreted) give the logits of the jax.numpy step.
    The two sum the readout over d_state in their own orders: they read
    0.9e-6 to 1.8e-6 of the largest logit apart (2 seeds of weights, 3 of
    tokens), and the bound is a third of ``TOL``.  The batcher counts the
    layers the kernel serves: none on the CPU, all 9 when it engages."""
    from repro.models import mamba

    sh, cfg, params = kernel_model
    toks = tokens_for(sh, 16, seed=3)
    want = served_logits(cfg, params, toks, 16)
    stats = ContinuousBatcher(cfg, params, n_slots=2, max_len=32).run()
    assert stats.ssm_fused_layers == 0
    monkeypatch.setattr(mamba, "_fuses_state_step", lambda hd, n: True)
    got = served_logits(cfg, params, toks, 16)
    assert gap(got, want) <= TOL / 3
    cb = ContinuousBatcher(cfg, params, n_slots=2, max_len=32)
    cb.submit(Request(rid=0, prompt=np.asarray(toks[0, :6]), max_new=2))
    assert cb.run().ssm_fused_layers == 9


def test_vmapped_decode_takes_the_jnp_state_step(kernel_model, monkeypatch):
    """Mapped over slots by ``jax.vmap`` (as ``serve/engine.py`` decodes),
    the kernel's state step falls back to the jax.numpy step: the logits
    are those of the unfused program, bit for bit, and no layer counts
    as fused."""
    from repro.models import mamba

    sh, cfg, params = kernel_model
    toks = tokens_for(sh, 8, seed=4)
    _, cache = prefill(params, cfg, {"tokens": toks[:, :8]}, cache_len=12)

    def slot_major(path, a):                 # K/V are sequence-major
        axis = 2 if path[-1].key in ("k", "v") else 1
        return a if a.ndim == 0 else jnp.moveaxis(
            jnp.expand_dims(a, axis + 1), axis, 0)

    per_slot = jax.tree_util.tree_map_with_path(slot_major, cache)

    def decode(t, c):
        return decode_step(params, cfg, t[None], c)[0]

    mapped = jax.jit(jax.vmap(decode, in_axes=(0, {"layers": 0, "pos": None})))
    want = np.asarray(mapped(toks[:, 8:9], per_slot))
    monkeypatch.setattr(mamba, "_fuses_state_step", lambda hd, n: True)
    with mamba.fused_state_steps() as fused:
        got = np.asarray(jax.jit(jax.vmap(
            decode, in_axes=(0, {"layers": 0, "pos": None})))(
                toks[:, 8:9], per_slot))
    assert fused and sum(n for n, in fused) == 0
    np.testing.assert_array_equal(got, want)
