"""The check decides `correct`: sound runs pass, and runs with the timed
path broken underneath fail, as does the control (the reference computed in
float8 in the program's place).  Every run here is a whole run of a tiny
cell through the harness on the CPU, all but the search for a TPU; the
serving cell has the committed limit, the training cell limits set at its
own size (``conftest.TINY_TRAIN_LIMITS``)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import harness
from benchmarks.chip.reference import dense_lm
from benchmarks.chip.traffic import serve_batch, train_lm

from conftest import SERVE, TINY, TRAIN


def _broken_step(kind):
    import repro.training.loop as loop

    orig = loop.make_train_step

    def make(*args, **kwargs):
        step = orig(*args, **kwargs)

        def broken(state, batch, *rest):
            if kind == "half_batch":
                half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
                return step(state, half, *rest)
            _, metrics = step(state, batch, *rest)
            return state, metrics                  # state left unchanged

        return broken

    return loop, "make_train_step", make


def _broken_decode(kind):
    from repro.training.serving import ContinuousBatcher

    orig = ContinuousBatcher._model_decode

    def decode(self, tok):
        logits, cache = orig(self, tok)
        if kind == "token_altered":
            return jnp.roll(logits, 1, axis=-1), cache   # the next id wins
        return logits, self.cache                    # cache left unchanged

    return ContinuousBatcher, "_model_decode", decode


FAULTS = {
    "sound_train": (TRAIN, None),
    "state_unchanged": (TRAIN, lambda: _broken_step("state_unchanged")),
    "half_batch": (TRAIN, lambda: _broken_step("half_batch")),
    "sound_serve": (SERVE, None),
    "token_altered": (SERVE, lambda: _broken_decode("token_altered")),
    "cache_unchanged": (SERVE, lambda: _broken_decode("cache_unchanged")),
}


@pytest.mark.parametrize("case", list(FAULTS))
def test_fault_fails_the_check(case, bench_root, monkeypatch):
    cell, fault = FAULTS[case]
    if fault is not None:
        monkeypatch.setattr(*fault())
    result = harness.run_cell(bench_root, cell, 2**31 + 99, 0.3, False,
                              time.perf_counter(), devices=jax.devices()[:1])
    assert result["correct"] is (fault is None), result["checks"]
    assert list(result)[-1] == "checks"
    if fault is None:
        assert result["attempted"] > 0 and result["failed"] == 0
        # set-up's phases in the order they end, the window's start last
        phases = [v for k, v in result["setup_phases"].items()
                  if k != "check_capture"]
        assert phases == sorted(phases)
        assert result["metrics"]["setup_s"]["value"] == pytest.approx(
            result["setup_phases"]["window"]
            - result["setup_phases"]["check_capture"])


def test_control_fails_training(bench_root):
    cell = harness.load_cell(bench_root, TRAIN)
    t = cell.traffic
    seed = harness.jax_seed(5)
    corpus = train_lm.SyntheticLMDataset(TINY["vocab_size"], 4096, seed=5)
    it = train_lm.lm_batch_iterator(corpus, t["global_batch"], t["seq_len"], 6)
    rows = [next(it)["tokens"] for _ in range(train_lm.CHECK_STEPS)]
    ref = train_lm.reference_readings(TINY, t, seed, rows, "f32")
    ctl = train_lm.reference_readings(TINY, t, seed, rows, "fp8")
    gaps = train_lm.gaps(ctl, ref)
    assert any(v > cell.limits[n]["limit"] for n, v in gaps.items()), gaps


def test_control_fails_serving(bench_root):
    cell = harness.load_cell(bench_root, SERVE)
    p = cell.traffic["prompt_len"]
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, TINY["vocab_size"], p + 9).astype(np.int32)
            for _ in range(2)]
    ref = serve_batch.reference_rows(TINY, 11, seqs, p, "f32")
    ctl = serve_batch.reference_rows(TINY, 11, seqs, p, "fp8")
    gap = max(dense_lm.widest_gap(r, np.asarray(jnp.argmax(c, -1)))
              for r, c in zip(ref, ctl))
    assert gap > cell.limits["token_gap"]["limit"]
