"""Offline batch generation: closed waves of requests through the program's
``ContinuousBatcher``, driven by its ``step()``.

The mix's data file gives the slots, the cache length, the prompt length,
the tokens each request asks for and how many requests the check compares.
Every request has the same prompt length and output length and no stop
token, so a wave fills every slot, all its requests retire on the same step
and the next wave is admitted whole: the window runs the same two programs
throughout, one prefill of (slots, prompt) and one decode step.  Prompts
are slices of the synthetic corpus at offsets drawn from the seed; the
weights are the reference's serving weights, made on the device in one
jitted call from the seed.

Set-up warms both programs with one wave of two-token requests and queues
every wave the window can reach (``wave_s_min`` lies under a wave's time
at the decode roofline, 256 steps of 12.2 ms).  The window then opens on the
admission of the first measured wave and does nothing but call ``step()``
and read the clock.  A token is served when ``step()`` returns with it; the
tokens, decode positions and prefill rows of the window are worked out from
the requests' state once it has closed.  The first token a prefill picks is
fed to the next decode but never appended to a request's output (see
PERF.md), so the subclass below records it for the check; it changes no
program.
"""
from __future__ import annotations

import math
from functools import partial

import numpy as np

from benchmarks.chip.harness import (BenchmarkError, Check, Outcome,
                                     free_device_memory, jax_seed,
                                     memory_peak_bytes, now)
from benchmarks.chip.reference import dense_lm
from benchmarks.chip.traffic import program
from benchmarks.chip.traffic.synthetic import SyntheticLMDataset


def _batcher_class():
    from repro.training.serving import ContinuousBatcher

    class Batcher(ContinuousBatcher):
        """The program's batcher; records each request's first token."""
        first_tokens: dict
        admitted = False

        def _model_prefill(self, batch):
            self.admitted = True
            return super()._model_prefill(batch)

        def _model_decode(self, tok):
            if self.admitted:
                self.admitted = False
                for i, r in enumerate(self.slots):
                    if r is not None and not r.done and not r.generated:
                        self.first_tokens[r.rid] = int(self.next_tok[i, 0])
            return super()._model_decode(tok)

    return Batcher


class Requests:
    """Requests from the seed: prompt ``i`` is a corpus slice at an offset
    drawn in order, so every run of a seed sends the same requests."""

    def __init__(self, corpus: np.ndarray, prompt_len: int, seed: int):
        self.corpus = corpus
        self.prompt_len = prompt_len
        self.rng = np.random.default_rng(seed)

    def prompt(self) -> np.ndarray:
        start = int(self.rng.integers(0, len(self.corpus) - self.prompt_len))
        return self.corpus[start:start + self.prompt_len].astype(np.int32)


def run(ctx, devices) -> Outcome:
    import jax

    from repro.training.serving import Request

    c, t = ctx.cell.config, ctx.cell.traffic
    sh = dense_lm.Shapes.from_config(c)
    seed = jax_seed(ctx.seed)
    params = jax.jit(partial(dense_lm.bench_program_tree, sh))(
        jax.random.PRNGKey(seed))
    corpus = SyntheticLMDataset(c["vocab_size"], t["corpus_tokens"],
                                seed=ctx.seed).tokens
    reqs = Requests(corpus, t["prompt_len"], ctx.seed + 1)
    jax.block_until_ready(params)
    ctx.mark("inputs")
    slots, max_new = t["slots"], t["max_new"]
    b = _batcher_class()(program.model_config(c), params, n_slots=slots,
                         max_len=t["max_len"])
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
    served = {r.rid for r in admitted if r.generated}
    finished = [r.rid for r in admitted if r.done and r.rid in served]
    bad = [r for r in finished if not _well_formed(sent[r], max_new,
                                                   c["vocab_size"])]
    sample_rng = np.random.default_rng([ctx.seed, 2])
    sample = sample_rng.choice(sorted(finished),
                               size=min(t["check_requests"], len(finished)),
                               replace=False) if finished else []
    sequences = [served_sequence(sent[int(r)], b.first_tokens[int(r)])
                 for r in sample]
    del b, params
    free_device_memory()

    gap = (token_gap(c, seed, sequences, t["prompt_len"]) if sequences
           else float("nan"))
    limits = ctx.cell.limits
    checks = [Check("token_gap", gap, limits["token_gap"]["limit"]),
              Check("bad_answers", float(len(bad)), 0.0)]
    step_ms = np.diff(stamps) * 1e3
    slowest = int(np.argmax(step_ms))
    return Outcome(
        end_to_end={"serve_tokens_per_s": tokens / window_s},
        attempted=len(finished), failed=len(bad), checks=checks,
        memory_peak_bytes=memory,
        facts={"tokens": tokens, "window_s": window_s,
               "decode_positions": positions,
               "prefill_rows": len(admitted),
               "prompt_len": t["prompt_len"],
               "decode_steps": len(positions),
               "host": {"step_ms_p50": float(np.percentile(step_ms, 50)),
                        "step_ms_max": float(step_ms[slowest]),
                        "slowest_step": slowest,
                        "slowest_step_admits": slowest % max_new == 0},
               "check_sequences": [q.tolist() for q in sequences]})


def decode_positions(admitted, slots: int, prompt_len: int) -> list:
    """Per decode step of the window, the position each live slot decodes
    at, from the requests' state after it.  The waves are admitted whole, in
    the order sent: wave ``w`` is requests ``w * slots`` to
    ``(w + 1) * slots - 1``, and its ``k``-th decode step feeds position
    ``prompt_len + k`` of every request that got a ``k + 1``-th token."""
    out = []
    for w in range(0, len(admitted), slots):
        wave = admitted[w:w + slots]
        for k in range(max(len(r.generated) for r in wave)):
            out.append([prompt_len + k for r in wave if len(r.generated) > k])
    return out


def _well_formed(req, max_new: int, vocab: int) -> bool:
    g = np.asarray(req.generated)
    return len(g) == max_new and bool(np.all((g >= 0) & (g < vocab)))


def served_sequence(req, first_token: int) -> np.ndarray:
    """The prompt, then every token served for it: the prefill's pick and
    the request's output."""
    return np.concatenate([req.prompt, [first_token],
                           np.asarray(req.generated, np.int32)]).astype(np.int32)


def reference_rows(c: dict, seed: int, sequences, prompt_len: int,
                   numerics: str):
    """The reference's (or, with ``numerics="fp8"``, the control's) logits
    at the served positions of each sequence, from the serving weights made
    again from the seed."""
    import jax

    sh = dense_lm.Shapes.from_config(c)
    tree = jax.jit(partial(dense_lm.bench_program_tree, sh))(
        jax.random.PRNGKey(seed))
    take = jax.jit(dense_lm.layer_of)
    with jax.default_matmul_precision("highest"):
        return dense_lm.served_logits(
            sh, numerics, lambda l: dense_lm.as_f32(take(tree, l)),
            dense_lm.as_f32(dense_lm.head_of(tree)), sequences, prompt_len)


def token_gap(c: dict, seed: int, sequences, prompt_len: int) -> float:
    """Widest gap, over the sampled requests, by which a served token's
    reference logit lies below the reference's best."""
    rows = reference_rows(c, seed, sequences, prompt_len, "f32")
    return max(dense_lm.widest_gap(r, s[prompt_len:])
               for r, s in zip(rows, sequences))


def control_readings(cell, seed: int, sequences) -> dict:
    """Readings of the control and of a planted fault on the sequences a
    run served: ``control``, the widest gap of the token the float8
    reference puts first; ``token_altered``, of the id after the best."""
    import jax.numpy as jnp

    c, p = cell.config, cell.traffic["prompt_len"]
    seqs = [np.asarray(q, np.int32) for q in sequences]
    ref = reference_rows(c, jax_seed(seed), seqs, p, "f32")
    ctl = reference_rows(c, jax_seed(seed), seqs, p, "fp8")
    return {
        "control": max(dense_lm.widest_gap(r, jnp.argmax(k, -1))
                       for r, k in zip(ref, ctl)),
        "token_altered": max(
            dense_lm.widest_gap(r, (jnp.argmax(r, -1) + 1) % c["vocab_size"])
            for r in ref),
    }
