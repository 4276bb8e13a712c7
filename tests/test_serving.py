"""Continuous-batching scheduler tests."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params
from repro.training.serving import ContinuousBatcher, Request


@pytest.fixture(scope="module")
def served():
    cfg = get_config("h2o-danube-1.8b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_completes_all_requests(served):
    cfg, params = served
    cb = ContinuousBatcher(cfg, params, n_slots=3, max_len=96)
    rng = np.random.default_rng(0)
    for rid in range(7):  # more requests than slots => refills must happen
        L = int(rng.integers(4, 12))
        cb.submit(Request(rid=rid, prompt=rng.integers(
            1, cfg.vocab_size, size=L).astype(np.int32), max_new=6))
    stats = cb.run(max_ticks=200)
    assert stats.completed == 7
    assert stats.prefills >= 2          # continuous refill happened
    assert stats.tokens_out == 7 * 6
    assert all(len(r.generated) == 6 for r in cb.slots if r is not None)


def test_stop_token_terminates_early(served):
    cfg, params = served
    cb = ContinuousBatcher(cfg, params, n_slots=2, max_len=64)
    # stop on whatever token the model emits first => finishes in 1 step
    cb.submit(Request(rid=0, prompt=np.array([5, 6, 7], np.int32), max_new=50))
    cb.step()
    first_tok = cb.slots[0].generated[0]
    cb2 = ContinuousBatcher(cfg, params, n_slots=2, max_len=64)
    cb2.submit(Request(rid=0, prompt=np.array([5, 6, 7], np.int32),
                       max_new=50, stop_token=first_tok))
    stats = cb2.run(max_ticks=100)
    assert stats.completed == 1
    assert len([t for r in cb2.slots if r for t in r.generated]) == 1


def test_continuation_is_deterministic(served):
    cfg, params = served
    prompts = [np.array([3, 4, 5, 6], np.int32)]
    outs = []
    for _ in range(2):
        cb = ContinuousBatcher(cfg, params, n_slots=1, max_len=64)
        cb.submit(Request(rid=0, prompt=prompts[0], max_new=8))
        cb.run(max_ticks=50)
        outs.append(tuple(cb.slots[0].generated))
    assert outs[0] == outs[1]


def test_refill_does_not_stall_live_requests(served):
    """A long request keeps generating one token per tick straight through
    the refills that admit later short requests — progress never resets."""
    cfg, params = served
    cb = ContinuousBatcher(cfg, params, n_slots=2, max_len=96)
    long_req = Request(rid=0, prompt=np.array([3, 4, 5], np.int32), max_new=16)
    cb.submit(long_req)
    for rid in range(1, 5):
        cb.submit(Request(rid=rid, prompt=np.array([7, 8], np.int32),
                          max_new=3))
    progress = []
    for _ in range(200):
        cb.step()
        progress.append(len(long_req.generated))
        if not cb.queue and all(r is None or r.done for r in cb.slots):
            break
    # strictly +1 per tick while live: no tick lost to a refill
    grew = [b - a for a, b in zip(progress, progress[1:]) if b != a or a < 16]
    assert progress[0] == 1
    assert all(d == 1 for d in grew[:15])
    assert long_req.done and len(long_req.generated) == 16
    assert cb.stats.completed == 5
    assert cb.stats.prefills >= 2


def test_stop_token_vs_max_new_termination(served):
    """stop_token ends a request the step it fires; an unmatched stop_token
    falls back to exactly max_new tokens."""
    cfg, params = served
    cb = ContinuousBatcher(cfg, params, n_slots=2, max_len=64)
    never = Request(rid=0, prompt=np.array([5, 6, 7], np.int32), max_new=4,
                    stop_token=-1)  # tokens are >= 0: can never match
    cb.submit(never)
    cb.run(max_ticks=100)
    assert never.done and len(never.generated) == 4

    first_tok = never.generated[0]
    cb2 = ContinuousBatcher(cfg, params, n_slots=2, max_len=64)
    stopped = Request(rid=0, prompt=np.array([5, 6, 7], np.int32),
                      max_new=50, stop_token=first_tok)
    cb2.submit(stopped)
    cb2.run(max_ticks=100)
    assert stopped.done
    assert stopped.generated[-1] == first_tok
    assert len(stopped.generated) < 50


def test_ragged_left_padded_prompts(served):
    """Ragged prompt lengths batch via left-padding: every request finishes
    with its full budget and the batched schedule is deterministic."""
    cfg, params = served
    lens = [1, 3, 9, 14]
    runs = []
    for _ in range(2):
        cb = ContinuousBatcher(cfg, params, n_slots=4, max_len=96)
        rng = np.random.default_rng(42)
        for rid, L in enumerate(lens):
            cb.submit(Request(rid=rid, prompt=rng.integers(
                1, cfg.vocab_size, size=L).astype(np.int32), max_new=5))
        stats = cb.run(max_ticks=100)
        assert stats.completed == len(lens)
        assert all(len(r.generated) == 5 for r in cb.slots if r is not None)
        assert all(0 <= t < cfg.vocab_size
                   for r in cb.slots if r is not None for t in r.generated)
        runs.append([tuple(r.generated) for r in cb.slots])
    assert runs[0] == runs[1]


def test_queue_is_fifo_deque(served):
    """The request queue is a deque admitted in FIFO order."""
    from collections import deque
    cfg, params = served
    cb = ContinuousBatcher(cfg, params, n_slots=2, max_len=64)
    assert isinstance(cb.queue, deque)
    for rid in range(5):
        cb.submit(Request(rid=rid, prompt=np.array([2, 3], np.int32),
                          max_new=2))
    cb.step()
    admitted_first = sorted(r.rid for r in cb.slots if r is not None)
    assert admitted_first == [0, 1]
    assert [r.rid for r in cb.queue] == [2, 3, 4]


def test_serve_stats_metrics_bridge(served):
    """run() publishes ServeStats into the obs metrics registry."""
    from repro.obs.metrics import MetricsRegistry
    cfg, params = served
    cb = ContinuousBatcher(cfg, params, n_slots=2, max_len=64)
    cb.submit(Request(rid=0, prompt=np.array([4, 5], np.int32), max_new=3))
    cb.run(max_ticks=50)
    reg = MetricsRegistry()
    cb.publish_stats(reg)
    stats = reg.serve_stats()
    assert stats["completed"] == 1.0
    assert stats["tokens_out"] == 3.0
    assert stats["decode_steps"] == cb.stats.decode_steps


def _profiled_spans(log_dir) -> list:
    """``[(name, start_ns, end_ns)]`` of the program's ``serve/*`` spans in
    the profiler trace written under ``log_dir``."""
    import glob
    import os

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                        recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith("serve/")]


def test_step_spans_under_profiler(served, tmp_path):
    """Under a profiler session, with the flight recorder off, every tick
    writes its spans into the trace: one ``serve/decode`` per decode step,
    and every fetch and emit inside a ``serve/step``."""
    cfg, params = served
    cb = ContinuousBatcher(cfg, params, n_slots=2, max_len=64)
    for rid in range(3):           # a third request forces a refill
        cb.submit(Request(rid=rid, prompt=np.array([4, 5, 6], np.int32),
                          max_new=3))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        cb.run(max_ticks=50)
    spans = _profiled_spans(tmp_path)
    names = [n for n, _, _ in spans]
    assert names.count("serve/decode") == cb.stats.decode_steps
    assert names.count("serve/prefill") == cb.stats.prefills == 2
    assert names.count("serve/fetch") == cb.stats.decode_steps + 2
    steps = [(a, b) for n, a, b in spans if n == "serve/step"]
    for n, a, b in spans:
        if n in ("serve/fetch", "serve/emit"):
            assert any(s <= a and b <= e for s, e in steps), (n, a, b)


def test_programs_are_named(served):
    """The two step programs read ``jit_prefill`` and ``jit_decode`` in a
    profile, not ``jit__lambda``."""
    import jax.numpy as jnp

    cfg, params = served
    cb = ContinuousBatcher(cfg, params, n_slots=2, max_len=64)
    cb.submit(Request(rid=0, prompt=np.array([4, 5, 6], np.int32), max_new=2))
    cb.step()
    prefill = cb._prefill.lower(
        params, {"tokens": jnp.zeros((2, 3), jnp.int32)}).as_text()
    decode = cb._decode.lower(params, jnp.asarray(cb.next_tok),
                              cb.cache).as_text()
    assert "module @jit_prefill" in prefill
    assert "module @jit_decode" in decode
