"""Offline batch generation on a hybrid of Mamba-2 and attention layers:
closed waves of requests through the program's ``ContinuousBatcher``,
driven by its ``step()``, as ``serve_batch`` runs them for a dense model.

The mix's data file gives the slots, the cache length, the prompt length,
the tokens each request asks for and how many requests the check compares.
Every request of a wave has the same prompt length and no stop token, so a
wave fills every slot and no admission left-pads a context: the batcher
refuses one for a model with SSM layers, whose recurrent state the pad
tokens would enter.  The model configuration is built here from the
configuration file (``program.model_config`` describes dense models only);
the weights are ``reference/granite_hybrid.py``'s serving weights, made on
the device in one jitted call from the seed, and the check compares the
served tokens with that reference's full forward over prompt and output.
The batcher donates its cache to each decode step (``donate_cache``), as a
deployment holding 2.4 GB of recurrent state would: no step allocates a
second cache beside the first.

Set-up warms both programs with one wave of two-token requests and queues
every wave the window can reach (``wave_s_min`` lies under a wave's time at
the decode roofline).  The window opens on the admission of the first
measured wave and does nothing but call ``step()`` and read the clock.  A
wave here outlasts a traced run's window, so once the window has closed the
wave in flight runs to its end, untimed, and every request the window
admitted is checked whole.
"""
from __future__ import annotations

import math
from functools import partial

import numpy as np

from benchmarks.chip.harness import (BenchmarkError, Check, Outcome,
                                     free_device_memory, jax_seed,
                                     memory_peak_bytes, now)
from benchmarks.chip.reference import dense_lm, granite_hybrid
from benchmarks.chip.traffic.serve_batch import (Requests, _batcher_class,
                                                 _well_formed, decode_positions,
                                                 served_sequence)
from benchmarks.chip.traffic.synthetic import SyntheticLMDataset


def model_config(c: dict):
    """``repro.configs.base.ModelConfig`` of a hybrid configuration file.
    A program whose configuration has no field for one of the model's
    mechanisms cannot run it: that is a ``BenchmarkError``."""
    from repro.configs.base import (ATTN_GLOBAL, MAMBA, MambaConfig,
                                    ModelConfig)

    sh = granite_hybrid.Shapes.from_config(c)
    if sh.d_inner % sh.d:
        raise BenchmarkError(f"{c['name']}: mamba inner width {sh.d_inner} "
                             f"is not a multiple of the hidden size")
    kinds = {granite_hybrid.MAMBA: MAMBA, granite_hybrid.ATTENTION: ATTN_GLOBAL}
    try:
        return ModelConfig(
            name=c["name"], family="hybrid", citation=c["source"],
            num_layers=sh.layers, d_model=sh.d, num_heads=sh.heads,
            num_kv_heads=sh.kv_heads, head_dim=sh.head_dim, d_ff=sh.ff,
            vocab_size=sh.vocab,
            layer_pattern=tuple(kinds[k] for k in sh.layer_types[:sh.period]),
            mamba=MambaConfig(d_state=sh.d_state, d_conv=sh.d_conv,
                              expand=sh.d_inner // sh.d,
                              head_dim=sh.ssm_head_dim, n_groups=sh.groups,
                              chunk_size=sh.chunk),
            nope=c["position_embedding_type"] == "nope",
            attention_multiplier=sh.attention_multiplier,
            embedding_multiplier=sh.embedding_multiplier,
            residual_multiplier=sh.residual_multiplier,
            logits_scaling=sh.logits_scaling,
            mlp_act=c["hidden_act"], mlp_gated=True,
            tie_embeddings=bool(c["tie_word_embeddings"]), norm_eps=sh.eps,
            dtype=c["dtype"])
    except TypeError as e:
        raise BenchmarkError(f"the program cannot describe {c['name']}: {e}")


def run(ctx, devices) -> Outcome:
    import jax

    from repro.training.serving import Request

    c, t = ctx.cell.config, ctx.cell.traffic
    cfg = model_config(c)
    sh = granite_hybrid.Shapes.from_config(c)
    seed = jax_seed(ctx.seed)
    params = jax.jit(partial(granite_hybrid.bench_program_tree, sh))(
        jax.random.PRNGKey(seed))
    corpus = SyntheticLMDataset(sh.vocab, t["corpus_tokens"],
                                seed=ctx.seed).tokens
    reqs = Requests(corpus, t["prompt_len"], ctx.seed + 1)
    jax.block_until_ready(params)
    ctx.mark("inputs")
    slots, max_new = t["slots"], t["max_new"]
    b = _batcher_class()(cfg, params, n_slots=slots, max_len=t["max_len"],
                         donate_cache=True)
    b.first_tokens = {}

    # set-up: one wave of two-token requests compiles (or loads) the prefill
    # and the decode program and every small op step() runs
    for i in range(slots):
        b.submit(Request(rid=-1 - i, prompt=reqs.prompt(), max_new=2))
    while b.queue or any(r is not None and not r.done for r in b.slots):
        b.step()
    ctx.mark("warm")

    waves = math.ceil(ctx.seconds / t["wave_s_min"]) + 1
    sent = [Request(rid=i, prompt=reqs.prompt(), max_new=max_new)
            for i in range(waves * slots)]
    for r in sent:
        b.submit(r)
    tokens0, steps0 = b.stats.tokens_out, b.stats.decode_steps
    t0 = ctx.open_window()
    clock = t0
    stamps = [t0]
    while clock - t0 < ctx.seconds:
        with ctx.window.annotate("bench/step"):
            b.step()
        clock = now()
        stamps.append(clock)
    window_s = ctx.window.close() - t0
    memory = memory_peak_bytes(devices)
    if not b.queue:
        raise BenchmarkError(f"the {waves} queued waves ran out inside the "
                             f"window: lower wave_s_min")

    tokens = b.stats.tokens_out - tokens0
    admitted = sent[:len(sent) - len(b.queue)]
    positions = decode_positions(admitted, slots, t["prompt_len"])
    if len(positions) != b.stats.decode_steps - steps0:
        raise BenchmarkError("the window's requests did not run in whole "
                             "waves")
    # a wave outlasts a traced run's window: the wave in flight runs to its
    # end after the window, on the same programs, so that the check always
    # has whole requests to compare (the harness counts it as the check's)
    while any(not r.done for r in admitted):
        b.step()
    finished = [r.rid for r in admitted]
    bad = [r for r in finished if not _well_formed(sent[r], max_new, sh.vocab)]
    sample_rng = np.random.default_rng([ctx.seed, 2])
    sample = sample_rng.choice(sorted(finished),
                               size=min(t["check_requests"], len(finished)),
                               replace=False) if finished else []
    sequences = [served_sequence(sent[int(r)], b.first_tokens[int(r)])
                 for r in sample]
    state_bytes = {"ssm_state_bytes": b.stats.ssm_state_bytes,
                   "kv_cache_bytes": b.stats.kv_cache_bytes}
    del b, params
    free_device_memory()

    gap = (token_gap(c, seed, sequences, t["prompt_len"]) if sequences
           else float("nan"))
    checks = [Check("token_gap", gap, ctx.cell.limits["token_gap"]["limit"]),
              Check("bad_answers", float(len(bad)), 0.0)]
    step_ms = np.diff(stamps) * 1e3
    slowest = int(np.argmax(step_ms))
    # the steps that admit a wave (and prefill it) apart from the rest, and
    # the time the rest spent above their median: where a slow run lost it
    admits = step_ms[::max_new]
    decodes = np.delete(step_ms, np.arange(0, len(step_ms), max_new))
    return Outcome(
        end_to_end={"serve_tokens_per_s": tokens / window_s},
        attempted=len(finished), failed=len(bad), checks=checks,
        memory_peak_bytes=memory,
        facts=dict(state_bytes, tokens=tokens, window_s=window_s,
                   slots=slots, max_len=t["max_len"],
                   decode_positions=positions, prefill_rows=len(admitted),
                   prompt_len=t["prompt_len"], decode_steps=len(positions),
                   host={"step_ms_p50": float(np.percentile(step_ms, 50)),
                         "step_ms_max": float(step_ms[slowest]),
                         "slowest_step": slowest,
                         "slowest_step_admits": slowest % max_new == 0,
                         "admit_step_ms": admits.tolist(),
                         "decode_ms_p99": float(np.percentile(decodes, 99)),
                         "decode_excess_s": float(np.sum(
                             decodes - np.median(decodes)) / 1e3)},
                   check_sequences=[q.tolist() for q in sequences]))


def token_gap(c: dict, seed: int, sequences, prompt_len: int) -> float:
    """Widest gap, over the sampled requests, by which a served token's
    reference logit lies below the reference's best, in the row's standard
    deviations."""
    sh = granite_hybrid.Shapes.from_config(c)
    rows = granite_hybrid.reference_rows(sh, seed, sequences, prompt_len, "f32")
    return max(dense_lm.widest_gap(r, s[prompt_len:])
               for r, s in zip(rows, sequences))


def control_readings(cell, seed: int, sequences) -> dict:
    """Readings of the control and of a planted fault on the sequences a
    run served: ``control``, the widest gap of the token the float8
    reference puts first; ``token_altered``, of the id after the best."""
    import jax.numpy as jnp

    c, p = cell.config, cell.traffic["prompt_len"]
    sh = granite_hybrid.Shapes.from_config(c)
    seqs = [np.asarray(q, np.int32) for q in sequences]
    ref = granite_hybrid.reference_rows(sh, jax_seed(seed), seqs, p, "f32")
    ctl = granite_hybrid.reference_rows(sh, jax_seed(seed), seqs, p, "fp8")
    return {
        "control": max(dense_lm.widest_gap(r, jnp.argmax(k, -1))
                       for r, k in zip(ref, ctl)),
        "token_altered": max(
            dense_lm.widest_gap(r, (jnp.argmax(r, -1) + 1) % sh.vocab)
            for r in ref),
    }
