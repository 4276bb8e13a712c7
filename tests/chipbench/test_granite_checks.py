"""The hybrid cell's check decides `correct`: a sound run passes, and runs
with the timed path broken underneath fail (a token altered, the SSM state
handed back unchanged by each decode step), as does the control (the
reference computed in float8).  Every run is a whole run of a tiny hybrid
cell through the harness on the CPU, all but the search for a TPU, with
the committed limit of the chip cell."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import harness, hybrid_flops
from benchmarks.chip.calibrate_hybrid import stale_ssm_decode
from benchmarks.chip.reference import dense_lm, granite_hybrid

from hybrid_tiny import HYBRID, make_hybrid_root, tiny_hybrid

SEED = 2**31 + 77


@pytest.fixture(scope="module")
def hybrid_root(tmp_path_factory):
    return make_hybrid_root(tmp_path_factory.mktemp("hybrid"))


def _token_altered(orig):
    def decode(self, tok):
        logits, cache = orig(self, tok)
        return jnp.roll(logits, 1, axis=-1), cache       # the next id wins
    return decode


FAULTS = {"sound": None, "token_altered": _token_altered,
          "ssm_state_unchanged": stale_ssm_decode}


@pytest.mark.parametrize("case", list(FAULTS))
def test_fault_fails_the_check(case, hybrid_root, monkeypatch):
    from repro.training.serving import ContinuousBatcher

    fault = FAULTS[case]
    if fault is not None:
        monkeypatch.setattr(ContinuousBatcher, "_model_decode",
                            fault(ContinuousBatcher._model_decode))
    result = harness.run_cell(hybrid_root, HYBRID, SEED, 0.3, False,
                              time.perf_counter(), devices=jax.devices()[:1])
    assert result["correct"] is (fault is None), result["checks"]
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    if fault is None:
        assert result["attempted"] > 0 and result["failed"] == 0


def test_counters_equal_hybrid_flops(hybrid_root):
    """The batcher's ``ssm_state_bytes`` and ``kv_cache_bytes``, handed to
    the readers as facts, are what ``hybrid_flops`` counts for the cell."""
    cell = harness.load_cell(hybrid_root, HYBRID)
    gen = harness.load_generator(hybrid_root, cell.traffic["kind"])
    ctx = harness.Context(cell, SEED + 1, 0.2, harness.Window(False),
                          time.perf_counter())
    facts = gen.run(ctx, jax.devices()[:1]).facts
    c, t = cell.config, cell.traffic
    assert facts["ssm_state_bytes"] == hybrid_flops.ssm_state_bytes(c, t["slots"])
    assert facts["kv_cache_bytes"] == hybrid_flops.kv_cache_bytes(
        c, t["slots"], t["max_len"])
    assert facts["ssm_state_bytes"] > 0 and facts["kv_cache_bytes"] > 0


def test_control_fails_the_check(hybrid_root):
    cell = harness.load_cell(hybrid_root, HYBRID)
    sh = granite_hybrid.Shapes.from_config(tiny_hybrid())
    p = cell.traffic["prompt_len"]
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, sh.vocab, p + 9).astype(np.int32) for _ in range(2)]
    ref = granite_hybrid.reference_rows(sh, 11, seqs, p, "f32")
    ctl = granite_hybrid.reference_rows(sh, 11, seqs, p, "fp8")
    gap = max(dense_lm.widest_gap(r, np.asarray(jnp.argmax(c, -1)))
              for r, c in zip(ref, ctl))
    assert gap > cell.limits["token_gap"]["limit"]
