"""Bring-up check: the main path of this repo, run once on a TPU.

    python chip_smoke.py                # one chip: device, kernels, train, serve
    python chip_smoke.py --four-chips   # four chips: sharded efbv/dense vs one chip

Everything runs in this one process, and nothing falls back to another
device: on anything but a TPU the script names the platform it found and
exits non-zero.  Each phase prints its numbers on lines of their own; a
failed check raises and exits non-zero.  Only when every phase passed is the
last line of standard output the JSON object
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Phases of the one-chip run:

* device:  ``jax.devices()[0]`` must be a TPU.
* kernels: every ``kernels/ops.py`` wrapper at one h2o-danube-1.8b MLP leaf
  (2560 x 6912), compiled (its program must hold a ``tpu_custom_call``) and
  compared with ``kernels/ref.py``: bit-identical where the code claims it,
  within the tolerances below elsewhere.
* train:   ``training.loop.train`` with h2o-danube-1.8b at published widths,
  depth cut to fit one chip: 5 dense steps, then 5 efbv steps with the
  ``qsgd_kernel`` compressor.
* serve:   the full 24-layer model in bf16 behind
  ``training.serving.ContinuousBatcher`` (4 slots, 6 requests), plus the
  cached decode step against a prefill over one more token.

``--four-chips`` runs only the sharded training of ``launch/sharded.py`` on a
(data=4, model=1) mesh and the same configurations on one chip with the 4
worker groups vmapped, and compares their losses.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

ARCH = "h2o-danube-1.8b"
D_IN, D_OUT = 2560, 6912        # h2o-danube-1.8b d_model x d_ff: one MLP leaf
CALIB_TOKENS = 128              # calibration rows for the fused prune scores

# Tolerances where the code claims no bit-identity.  A quantized value may
# land one level off the oracle where kernel and oracle divide by the scale
# with a different rounding (floor of x/scale + noise sits on an integer);
# a prune mask may flip where a recomputed score ties its column threshold.
QUANT_FLIP_FRACTION = 1e-4      # elements off by one level, at most
SCALE_RTOL = 1e-6               # per-block absmax scale
PRUNE_FLIP_FRACTION = 1e-4      # mask entries that differ, at most
INIT_LOSS_ATOL = 1.0            # |step-0 loss - ln(vocab)| for a fresh init
DECODE_RTOL = 5e-2              # bf16 decode vs prefill logits, / max |logit|
FOUR_CHIP_LOSS_RTOL = 1e-3      # mesh vs vmapped losses, step by step

TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 4096, 5
# Depth is the one cut.  Dense: 4.4 GB of state and 9.4 GB of temporaries
# at 4 layers.  efbv keeps two fp32 error-feedback copies per worker group
# on top of AdamW; at 3 layers it compiles to 16.1 GB of the chip's 15.75,
# so 2 layers.  Its sync compresses leaf by leaf (bucket_size=0): the fused
# bucket path materialises ~5 extra fp32 copies of the whole gradient around
# the kernel (9.3 GB for the sync alone at one layer).
DENSE_LAYERS, EFBV_LAYERS = 4, 2
SERVE_SLOTS, SERVE_MAX_LEN, SERVE_NEW = 4, 128, 16
SERVE_PROMPTS = (16, 24, 40, 64, 32, 48)     # first wave pads to 64 tokens
FOUR_CHIP_LAYERS, FOUR_CHIP_STEPS = 1, 3


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(*parts) -> None:
    print(*parts, flush=True)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------
def device_phase(count: int) -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SmokeFailure(f"device: found platform {dev.platform!r} "
                           f"({dev.device_kind}); this check needs a TPU")
    check(len(devices) >= count,
          f"device: {len(devices)} TPU device(s), need {count}")
    say(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def peak_gb() -> float:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 1e9


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
def _compiled(name: str, fn, *args, **static):
    """Compile a jitted wrapper and require the Mosaic kernel in its program."""
    t = time.perf_counter()
    compiled = fn.lower(*args, **static).compile()
    check("tpu_custom_call" in compiled.as_text(),
          f"kernels/{name}: no tpu_custom_call in the compiled program")
    return compiled, time.perf_counter() - t


def _levels_off(name, got, want, scale, levels: int = 127):
    """Check ``got`` is ``want`` (in units of the block's ``scale``) but for
    rare one-level flips and the drift a SCALE_RTOL scale error gives the
    largest of ``levels``; return (flips, drift in levels)."""
    import numpy as np

    off = np.abs(got.astype(np.float64) - want.astype(np.float64)) / scale
    flipped = off > 0.5
    flips = int(np.count_nonzero(flipped))
    drift = float(np.max(off, where=~flipped, initial=0.0))
    check(float(off.max()) <= 1.0 + 1e-4,
          f"kernels/{name}: {float(off.max()):.3g} levels off the oracle")
    check(flips <= QUANT_FLIP_FRACTION * got.size,
          f"kernels/{name}: {flips} of {got.size} values a level off the oracle")
    check(drift <= levels * SCALE_RTOL,
          f"kernels/{name}: values drift {drift:.3g} levels off the oracle")
    return flips, drift


def _scored_ref(w, X, mode, sparsity=0.5, alpha=0.5, beta=0.5):
    """ref.wanda_prune_ref with the per-column thresholds ops.prune_scored uses."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref

    xnorm = jnp.sqrt(jnp.sum(X ** 2, axis=0))
    kw = {}
    if mode == "symwanda":
        ynorm = jnp.sqrt(jnp.sum((X @ w) ** 2, axis=0))
        aw = jnp.abs(w)
        kw = dict(beta=beta, ynorm=ynorm, mu_in=jnp.mean(aw * xnorm[:, None]),
                  mu_out=jnp.mean(aw * ynorm[None, :]))
    elif mode == "ria":
        kw = dict(alpha=alpha)
    scores = ref.wanda_scores_ref(w, xnorm, mode, **kw)
    k = max(1, int(round((1 - sparsity) * w.shape[0])))
    tau = jax.lax.top_k(scores.T, k)[0][:, -1]
    return ref.wanda_prune_ref(w, xnorm, tau, mode, **kw)


def kernels_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.comm import codecs
    from repro.core.compressors import qsgd_kernel
    from repro.kernels import ops, ref
    from repro.kernels.quant8 import QBLOCK, TILE_ROWS

    d = D_IN * D_OUT
    # whole (TILE_ROWS, QBLOCK) tiles: the wrappers pad nothing, so the
    # oracles see the kernels' own tiles and the same noise draw
    check(d % (TILE_ROWS * QBLOCK) == 0, "kernels: leaf does not tile evenly")
    kx, kq, km, kw, kx2 = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(kx, (d,), jnp.float32)
    x2d = x.reshape(-1, QBLOCK)
    noise = jax.random.uniform(kq, x2d.shape, jnp.float32)
    q_ref, s_ref = (np.asarray(a) for a in ref.quant_pack_ref(x2d, noise))
    row_scale = np.repeat(s_ref.reshape(-1), QBLOCK)

    def report(name, secs, **nums):
        fields = " ".join(f"{k}={v}" for k, v in nums.items())
        say(f"kernels {name} tpu_custom_call=yes compile_s={secs:.2f} {fields}")

    c, secs = _compiled("quantize_dequantize", ops.quantize_dequantize, x, kq)
    carrier = np.asarray(c(x, kq))
    want = np.asarray(ref.quant_dequant_ref(x2d, noise)).reshape(-1)
    flips, drift = _levels_off("quantize_dequantize", carrier, want, row_scale)
    report("quantize_dequantize", secs, d=d, level_flips_vs_ref=flips,
           drift_levels=f"{drift:.3g}")

    c, secs = _compiled("quantize_pack", ops.quantize_pack, x, kq)
    q_dev, s_dev = c(x, kq)
    q, scales = np.asarray(q_dev), np.asarray(s_dev)
    s_err = float(np.max(np.abs(scales - s_ref) / s_ref))
    check(s_err <= SCALE_RTOL, f"kernels/quantize_pack: scales rtol {s_err:.3g}")
    flips, _ = _levels_off("quantize_pack", q.reshape(-1), q_ref.reshape(-1),
                           np.ones(d))
    report("quantize_pack", secs, d=d, level_flips_vs_ref=flips,
           scale_rtol=f"{s_err:.3g}")

    c, secs = _compiled("stream_quantize_pack", ops.stream_quantize_pack, x, kq)
    q_s, scales_s = (np.asarray(a) for a in c(x, kq))
    check(np.array_equal(q_s, q) and np.array_equal(scales_s, scales),
          "kernels/stream_quantize_pack: planes differ from quantize_pack")
    report("stream_quantize_pack", secs, bitident_vs_quantize_pack="yes")

    c, secs = _compiled("unpack_dequantize", ops.unpack_dequantize, q_dev,
                        s_dev, d=d)
    dense = np.asarray(c(q_dev, s_dev))
    check(np.array_equal(dense, np.asarray(
        ref.unpack_dequant_ref(q_dev, s_dev)).reshape(-1)),
        "kernels/unpack_dequantize: differs from the oracle")
    check(np.array_equal(dense, carrier),
          "kernels/unpack_dequantize: decoded planes differ from the carrier")
    check(codecs.roundtrip_equal(qsgd_kernel(), kq, x),
          "kernels: codec decode of quantize_pack differs from qsgd_kernel")
    report("unpack_dequantize", secs, bitident_vs_ref="yes",
           bitident_vs_carrier="yes", codec_roundtrip="yes")

    mask = jax.random.bernoulli(km, 0.3, (d,))
    c, secs = _compiled("pack_bits", ops.pack_bits, mask)
    words = c(mask)
    want = ref.pack_mask_ref(mask.reshape(32, d // 32))[0]
    check(np.array_equal(np.asarray(words), np.asarray(want)),
          "kernels/pack_bits: words differ from the oracle")
    report("pack_bits", secs, words=int(words.shape[0]), bitident_vs_ref="yes")

    c, secs = _compiled("unpack_bits", ops.unpack_bits, words, d=d)
    bits = np.asarray(c(words))
    check(np.array_equal(bits, np.asarray(mask, np.uint32)),
          "kernels/unpack_bits: round trip differs from the mask")
    report("unpack_bits", secs, roundtrip_bitident="yes")

    w = jax.random.normal(kw, (D_IN, D_OUT), jnp.float32)
    scores = jnp.abs(w) * jax.random.uniform(kx2, w.shape)
    c, secs = _compiled("prune_nm", ops.prune_nm, w, scores)
    out, keep = (np.asarray(a) for a in c(w, scores))
    want_out, want_keep = (np.asarray(a) for a in ref.nm_prune_ref(w, scores))
    check(np.array_equal(keep, want_keep) and np.array_equal(out, want_out),
          "kernels/prune_nm: differs from the oracle")
    report("prune_nm", secs, shape=f"{D_IN}x{D_OUT}", bitident_vs_ref="yes")

    X = jax.random.normal(kx2, (CALIB_TOKENS, D_IN), jnp.float32)
    w_np = np.asarray(w)
    for mode in ("wanda", "ria", "symwanda"):
        c, secs = _compiled(f"prune_scored[{mode}]", ops.prune_scored, w, X,
                            mode=mode)
        out, keep = (np.asarray(a) for a in c(w, X))
        _, want_keep = _scored_ref(w, X, mode)
        flips = int(np.count_nonzero(keep != np.asarray(want_keep)))
        kept = float(keep.mean())
        check(flips <= PRUNE_FLIP_FRACTION * keep.size,
              f"kernels/prune_scored[{mode}]: {flips} mask entries differ")
        check(np.array_equal(out, w_np * keep),
              f"kernels/prune_scored[{mode}]: output is not w * mask")
        check(abs(kept - 0.5) <= 0.01,
              f"kernels/prune_scored[{mode}]: kept {kept:.4f}, want 0.5")
        report(f"prune_scored[{mode}]", secs, mask_flips_vs_ref=flips,
               kept=f"{kept:.4f}")


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------
def _train_run(label, layers, sync, *, n_groups=1, steps=TRAIN_STEPS,
               batch=TRAIN_BATCH, seq=TRAIN_SEQ, mesh=None, **tc_kw):
    """``training.loop.train`` at published widths, ``layers`` deep.
    Returns (final state, losses); the state passed in is donated each step."""
    from dataclasses import replace

    from repro.configs import get_config
    from repro.configs.base import TrainConfig
    from repro.data.synthetic import SyntheticLMDataset, lm_batch_iterator
    from repro.training.loop import train

    full = get_config(ARCH)
    cfg = replace(full, num_layers=layers)
    say(f"train {label} cut: {ARCH} num_layers {layers} of {full.num_layers} "
        f"(d_model {cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}x"
        f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, window "
        f"{cfg.sliding_window}), {cfg.param_count() / 1e6:.1f}M params, "
        f"batch {batch}x{seq}, sync {sync.mode}/{sync.compressor}")
    tc = TrainConfig(model=cfg, seq_len=seq, global_batch=batch, sync=sync,
                     **tc_kw)
    ds = SyntheticLMDataset(vocab_size=cfg.vocab_size, length=100_000, seed=0)
    t = time.perf_counter()
    state, history = train(cfg, tc, lm_batch_iterator(ds, batch, seq, seed=1),
                           n_groups=n_groups, steps=steps, log_every=steps,
                           mesh=mesh)
    losses = [h["loss"] for h in history]
    say(f"train {label} losses=" + ",".join(f"{v:.6f}" for v in losses)
        + f" wall_s={time.perf_counter() - t:.1f}")
    check(all(math.isfinite(v) for v in losses), f"train {label}: loss not finite")
    return state, losses


def train_phase() -> None:
    from repro.configs import get_config
    from repro.configs.base import SyncConfig

    ln_v = math.log(get_config(ARCH).vocab_size)
    runs = (("dense", DENSE_LAYERS, SyncConfig(mode="dense"), 1),
            ("efbv", EFBV_LAYERS, SyncConfig(mode="efbv", compressor="qsgd_kernel",
                                             bucket_size=0), 2))
    for label, layers, sync, groups in runs:
        state, losses = _train_run(label, layers, sync, n_groups=groups)
        del state   # free the chip for the next run
        check(abs(losses[0] - ln_v) <= INIT_LOSS_ATOL,
              f"train {label}: step-0 loss {losses[0]:.4f}, want ln(vocab) "
              f"= {ln_v:.4f} +- {INIT_LOSS_ATOL}")
        say(f"train {label} step0_loss={losses[0]:.6f} ln_vocab={ln_v:.6f} "
            f"peak_hbm_gb={peak_gb():.3f}")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def serve_phase(seed: int) -> None:
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.models import init_params
    from repro.training.serving import ContinuousBatcher, Request

    cfg = get_config(ARCH)
    t = time.perf_counter()
    params = jax.jit(lambda k: init_params(k, cfg))(jax.random.PRNGKey(seed))
    leaves = jax.tree_util.tree_leaves(params)
    n_bytes = sum(a.nbytes for a in leaves)
    say(f"serve model {ARCH} num_layers {cfg.num_layers} dtype "
        f"{leaves[0].dtype} params_gb={n_bytes / 1e9:.3f} "
        f"init_s={time.perf_counter() - t:.1f}")

    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, prompt=rng.integers(1, cfg.vocab_size, size=n)
                    .astype(np.int32), max_new=SERVE_NEW)
            for i, n in enumerate(SERVE_PROMPTS)]
    cb = ContinuousBatcher(cfg, params, n_slots=SERVE_SLOTS,
                           max_len=SERVE_MAX_LEN)
    for r in reqs:
        cb.submit(r)
    t = time.perf_counter()
    stats = cb.run(max_ticks=200)
    wall = time.perf_counter() - t
    say(f"serve requests={len(reqs)} slots={SERVE_SLOTS} completed="
        f"{stats.completed} tokens_out={stats.tokens_out} prefills="
        f"{stats.prefills} decode_steps={stats.decode_steps} wall_s={wall:.1f}")
    for r in reqs:
        say(f"serve request {r.rid} prompt={len(r.prompt)} generated="
            f"{len(r.generated)} first_ids={r.generated[:4]}")
        check(r.done and len(r.generated) == SERVE_NEW,
              f"serve: request {r.rid} retired with {len(r.generated)} tokens")
        check(all(0 <= tok < cfg.vocab_size for tok in r.generated),
              f"serve: request {r.rid} emitted an id outside the vocabulary")
    check(stats.completed == len(reqs), "serve: not every request completed")

    # the cached decode step agrees with a prefill over one more token
    # (tests/test_arch_smoke.py's check, here at full width in bf16)
    L = max(SERVE_PROMPTS[:SERVE_SLOTS])   # a prefill shape already compiled
    toks = rng.integers(1, cfg.vocab_size, size=(SERVE_SLOTS, L + 1)).astype(np.int32)
    _, cache = cb._prefill(params, {"tokens": toks[:, :L]})
    step_logits, _ = cb._decode(params, toks[:, L:], cache)
    full_logits, _ = cb._prefill(params, {"tokens": toks})
    a = np.asarray(full_logits[:, -1, :cfg.vocab_size], np.float32)
    b = np.asarray(step_logits[:, 0, :cfg.vocab_size], np.float32)
    rel = float(np.max(np.abs(a - b)) / np.max(np.abs(a)))
    same_top = int(np.sum(a.argmax(-1) == b.argmax(-1)))
    say(f"serve decode_vs_prefill rel_err={rel:.3g} (limit {DECODE_RTOL}) "
        f"argmax_agree={same_top}/{SERVE_SLOTS} peak_hbm_gb={peak_gb():.3f}")
    check(rel <= DECODE_RTOL, f"serve: decode differs from prefill by {rel:.3g}")


# ---------------------------------------------------------------------------
# four chips: compressed sync across data-parallel groups
# ---------------------------------------------------------------------------
def four_chip_phase() -> None:
    import jax
    from repro.configs.base import SyncConfig
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4, 1), ("data", "model"), devices=jax.devices()[:4])
    # full learning rate from step 0, so the 3 steps move the loss
    kw = dict(layers=FOUR_CHIP_LAYERS, steps=FOUR_CHIP_STEPS, warmup_steps=1)
    runs = (("efbv", SyncConfig(mode="efbv", compressor="qsgd_kernel",
                                bucket_size=0), 4),
            ("dense", SyncConfig(mode="dense"), 1))
    for label, sync, groups in runs:
        state, on_mesh = _train_run(f"{label}@mesh(data=4,model=1)", sync=sync,
                                    mesh=mesh, **kw)
        arrays = jax.tree_util.tree_leaves(state)
        spans = {len(a.sharding.device_set) for a in arrays}
        split = sum(not a.sharding.is_fully_replicated for a in arrays)
        say(f"four {label} state_arrays={len(arrays)} devices_spanned={sorted(spans)} "
            f"sharded_arrays={split}")
        check(spans == {4}, f"four {label}: state spans {sorted(spans)} devices")
        check(split > 0, f"four {label}: every state array is replicated")
        del state, arrays
        state, on_one = _train_run(f"{label}@one_chip(groups={groups})", sync=sync,
                                   n_groups=groups, **kw)
        check({len(a.sharding.device_set) for a in jax.tree_util.tree_leaves(state)}
              == {1}, f"four {label}: one-chip state is not on one device")
        del state
        rel = max(abs(a - b) / abs(b) for a, b in zip(on_mesh, on_one))
        say(f"four {label} loss_rel_diff_max={rel:.3g} (limit {FOUR_CHIP_LOSS_RTOL})")
        check(rel <= FOUR_CHIP_LOSS_RTOL,
              f"four {label}: mesh and one-chip losses differ by {rel:.3g}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded training and its "
                         "one-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    try:
        device = device_phase(4 if args.four_chips else 1)
        from repro.utils.compile_cache import use_compile_cache

        say(f"compile_cache dir={use_compile_cache()}")
        phases = ([("four", four_chip_phase)] if args.four_chips else
                  [("kernels", lambda: kernels_phase(args.seed)),
                   ("train", train_phase),
                   ("serve", lambda: serve_phase(args.seed))])
        for name, phase in phases:
            t = time.perf_counter()
            phase()
            say(f"phase {name} ok wall_s={time.perf_counter() - t:.1f}")
    except SmokeFailure as e:
        print(f"FAIL {e}", file=sys.stderr, flush=True)
        return 1
    say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
