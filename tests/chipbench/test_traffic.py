"""The traffic generators give the same inputs for the same seed, other
inputs for another seed, and take seeds beyond 32 bits."""
from __future__ import annotations

import numpy as np
import pytest

from benchmarks.chip.harness import jax_seed
from benchmarks.chip.traffic.serve_batch import Requests
from benchmarks.chip.traffic.synthetic import SyntheticLMDataset, lm_batch_iterator

BIG = 2**31 + 12345


def rows(seed, n=3):
    corpus = SyntheticLMDataset(256, 4096, seed=seed)
    it = lm_batch_iterator(corpus, 2, 32, seed=seed + 1)
    return [next(it)["tokens"] for _ in range(n)]


def prompts(seed, n=4):
    corpus = SyntheticLMDataset(256, 4096, seed=seed).tokens
    reqs = Requests(corpus, 16, seed + 1)
    return [reqs.prompt() for _ in range(n)]


@pytest.mark.parametrize("make", [rows, prompts], ids=["train_lm", "serve_batch"])
def test_same_seed_same_inputs(make):
    a, b = make(BIG), make(BIG)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    c = make(BIG + 1)
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))


def test_train_rows_shape_and_range():
    for r in rows(7):
        assert r.shape == (2, 33) and r.dtype == np.int32
        assert r.min() >= 0 and r.max() < 256


def test_jax_seed_fits_31_bits():
    seeds = [0, 1, 2**31 - 1, 2**31, 2**32 + 7, 3 * 2**40]
    mapped = [jax_seed(s) for s in seeds]
    assert all(0 <= m < 2**31 for m in mapped)
    assert len(set(mapped)) == len(seeds)
    assert jax_seed(BIG) == jax_seed(BIG)


def test_decode_positions_from_request_state():
    from benchmarks.chip.traffic.serve_batch import decode_positions
    from repro.training.serving import Request

    def req(n):
        return Request(rid=0, prompt=np.zeros(4, np.int32), max_new=3,
                       generated=[1] * n)

    # a finished wave of two, then a wave of two cut by the window's end
    admitted = [req(3), req(3), req(1), req(1)]
    assert decode_positions(admitted, 2, 4) == [[4, 4], [5, 5], [6, 6], [4, 4]]
    assert decode_positions([req(2), req(1)], 2, 10) == [[10, 10], [11]]
