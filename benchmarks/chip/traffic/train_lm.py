"""Training traffic: ``repro.training.loop.train`` itself, fed seeded rows.

The mix's data file gives the global batch and sequence length, the sync
mode, the optimizer as the configuration states it, remat and how often the
loop fetches its metrics.  Rows of ``seq_len + 1`` tokens are drawn at
random offsets of the synthetic corpus, every row from the seed.

One call of ``train()`` runs the whole cell.  Its first ``setup_steps``
steps are set-up: the first compiles (or loads from the compile cache), and
the first three are the ones the check follows.  The window opens once the
last of them has finished, and holds every step whose batch is drawn inside
it; the first draw after ``--seconds`` closes it, and the window ends when
the last step drawn has finished.  Tokens per second is every token of
those steps over that time.

The feed reads, at each draw, the state ``train()`` is about to hand to the
next step and the metrics of the steps so far: its locals ``state`` and
``history``.  That is how the check sees the very object the window drives
without a second program: the initial parameters, the first moment after
one step (which holds the first gradient as the optimizer got it) and the
parameters after three steps.
"""
from __future__ import annotations

import math
import sys

import numpy as np

from benchmarks.chip.harness import (BenchmarkError, Check, Outcome,
                                     free_device_memory, jax_seed,
                                     memory_peak_bytes, now)
from benchmarks.chip.reference import dense_lm
from benchmarks.chip.traffic import program
from benchmarks.chip.traffic.synthetic import (SyntheticLMDataset,
                                               lm_batch_iterator)

CHECK_STEPS = 3
# Leaves whose reference gradient is under this share of the median leaf's
# move by round-off alone and are left out of the parameter change.
STILL_LEAF = 1e-3


class WindowClosed(Exception):
    """Raised by the feed at the first draw after the window's time."""


class Feed:
    def __init__(self, ctx, rows, setup_steps: int, b1: float):
        self.ctx = ctx
        self.rows = rows
        self.setup_steps = setup_steps
        self.b1 = b1
        self.drawn = 0
        self.t0 = None
        self.check_rows = []
        self.p0 = self.p3 = self.first_grad = None
        self.last_state = self.history = None

    def __iter__(self):
        return self

    def __next__(self):
        import jax

        k = self.drawn
        frame = sys._getframe(1).f_locals
        if "state" not in frame or "history" not in frame:
            raise BenchmarkError("train() no longer holds `state` and "
                                 "`history` where the feed reads them")
        state = frame["state"]
        if k < 2:
            self.ctx.mark(("built", "first_step")[k])
        if k == 0:
            with self.ctx.check_capture():
                self.p0 = jax.device_get(state.params)
        elif k == 1:
            with self.ctx.check_capture():
                mu = program.stacked_norms(state.opt_state.mu)
                self.first_grad = {n: v / (1 - self.b1) for n, v in mu.items()}
        elif k == CHECK_STEPS:
            with self.ctx.check_capture():
                self.p3 = jax.device_get(state.params)
        if k == self.setup_steps:
            jax.block_until_ready(state)
            self.t0 = self.ctx.open_window()
        elif k > self.setup_steps and \
                now() - self.t0 >= self.ctx.seconds:
            self.last_state = state
            self.history = frame["history"]
            raise WindowClosed
        with self.ctx.window.annotate("bench/draw"):
            batch = next(self.rows)
        if k < CHECK_STEPS:
            self.check_rows.append(batch["tokens"])
        self.drawn += 1
        return batch


def run(ctx, devices) -> Outcome:
    import jax

    from repro.training.loop import train

    c, t = ctx.cell.config, ctx.cell.traffic
    if t["setup_steps"] <= CHECK_STEPS:
        raise BenchmarkError("setup_steps must exceed the checked steps")
    seed = jax_seed(ctx.seed)
    cfg = program.model_config(c)
    tc = program.train_config(cfg, t, seed)
    corpus = SyntheticLMDataset(c["vocab_size"], t["corpus_tokens"],
                                seed=ctx.seed)
    rows = lm_batch_iterator(corpus, t["global_batch"], t["seq_len"],
                             seed=ctx.seed + 1)
    feed = Feed(ctx, rows, t["setup_steps"], t["optimizer"]["b1"])
    mesh = None
    if len(devices) > 1:
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((len(devices), 1), ("data", "model"), devices=devices)
    try:
        train(cfg, tc, feed, steps=sys.maxsize, log_every=t["log_every"],
              mesh=mesh)
    except WindowClosed:
        pass
    else:
        raise BenchmarkError("train() ended before the window closed")
    jax.block_until_ready(feed.last_state)
    window_s = ctx.window.close() - feed.t0
    memory = memory_peak_bytes(devices)
    losses = [float(h["loss"]) for h in jax.device_get(feed.history)]
    in_window = losses[t["setup_steps"]:feed.drawn]
    steps = len(in_window)
    failed = sum(1 for x in in_window if not math.isfinite(x))
    tokens = steps * t["global_batch"] * t["seq_len"]
    p0, p3, first_grad = feed.p0, feed.p3, feed.first_grad
    check_rows = feed.check_rows
    feed.last_state = feed.history = feed.p0 = feed.p3 = None
    free_device_memory()

    readings = (losses[:CHECK_STEPS], first_grad,
                program.host_change_norms(p0, p3))
    ref = reference_readings(c, t, seed, check_rows, "f32")
    checks = [Check(n, v, ctx.cell.limits[n]["limit"])
              for n, v in gaps(readings, ref).items()]
    return Outcome(
        end_to_end={"train_tokens_per_s": tokens / window_s},
        attempted=steps, failed=failed, checks=checks,
        memory_peak_bytes=memory,
        facts={"tokens": tokens, "steps": steps, "window_s": window_s,
               "seq_len": t["seq_len"], "global_batch": t["global_batch"],
               "check_rows": check_rows})


def reference_readings(c: dict, t: dict, seed: int, check_rows,
                       numerics: str):
    """The reference's (or, with ``numerics="fp8"``, the control's) losses,
    first-gradient norms and parameter-change norms over the checked steps,
    from the weights ``train()`` makes for ``seed``."""
    import jax

    sh = dense_lm.Shapes.from_config(c)
    with jax.default_matmul_precision("highest"):
        p0 = dense_lm.init_like_program(sh, jax.random.PRNGKey(seed))
        return dense_lm.train_steps(sh, numerics, p0, check_rows,
                                    t["optimizer"])


def gaps(readings, ref) -> dict:
    """The numbers the check compares, from (losses, first-gradient norms,
    change norms) of the program and of the reference.

    ``loss_gap``: the largest gap of a checked step's loss, in nats.
    ``grad_gap``: the worst leaf's gap between the first gradient's norms.
    ``change_gap``: the worst leaf's gap between the parameter changes over
    the checked steps, leaving out the leaves only round-off moves."""
    losses, grad, change = readings
    ref_losses, ref_grad, ref_change = ref
    med = float(np.median(list(ref_grad.values())))
    still = {n for n, g in ref_grad.items() if g < STILL_LEAF * med}
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(losses, ref_losses)),
        "grad_gap": dense_lm.worst_leaf_gap(grad, ref_grad),
        "change_gap": dense_lm.worst_leaf_gap(change, ref_change, skip=still),
    }


def control_readings(cell, seed: int, check_rows) -> dict:
    """The check's numbers for the control (the reference in float8 in the
    program's place) and for a planted fault (the reference in the
    program's place taking the mean over half of each batch)."""
    c, t = cell.config, cell.traffic
    s = jax_seed(seed)
    ref = reference_readings(c, t, s, check_rows, "f32")
    out = {"control": gaps(reference_readings(c, t, s, check_rows, "fp8"), ref)}
    half = [r[: len(r) // 2] for r in check_rows]
    out["half_batch"] = gaps(reference_readings(c, t, s, half, "f32"), ref)
    return out
