"""Production serving launcher: prefill + batched decode.

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-2.7b --dry-run \
      --shape decode_32k
  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-2.7b --reduced
"""
import argparse
import os
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="decode_32k",
                    choices=["prefill_32k", "decode_32k", "long_500k"])
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args()

    if args.dry_run:
        os.execv(sys.executable, [
            sys.executable, "-m", "repro.launch.dryrun",
            "--arch", args.arch, "--shape", args.shape,
            "--multi-pod", "multi" if args.multi_pod else "single",
        ])

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.models import decode_step, init_params, prefill
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(1, cfg.vocab_size, (args.batch, 16), dtype=np.int64).astype(np.int32))
    batch = {"tokens": prompt}
    if cfg.enc_layers:
        batch["src_embeds"] = 0.02 * jax.random.normal(
            jax.random.PRNGKey(1), (args.batch, 16, cfg.enc_d_model))
    if cfg.vision_tokens:
        batch["vision_embeds"] = 0.02 * jax.random.normal(
            jax.random.PRNGKey(2), (args.batch, cfg.vision_tokens, cfg.d_model))
    logits, cache = jax.jit(
        lambda p, b: prefill(p, cfg, b, cache_len=16 + args.gen + 1))(params, batch)
    step = jax.jit(lambda p, t, c: decode_step(p, cfg, t, c))
    tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], -1)[:, None].astype(jnp.int32)
    toks = []
    for _ in range(args.gen):
        logits, cache = step(params, tok, cache)
        tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], -1)[:, None].astype(jnp.int32)
        toks.append(np.asarray(tok[:, 0]))
    print("decoded:", np.stack(toks, 1).tolist())


if __name__ == "__main__":
    main()
