"""Training launcher for one host.

Trains through ``training.loop.train`` on the devices of this process: on a
host with several devices, over a mesh of all N of them with the sharded
step of launch/sharded.py, (data=N, model=1) or, for ``hier``, (pod=N): the
sync modes' worker groups are the N shards.  With one device, unsharded.
It does not call ``jax.distributed.initialize``, so it never spans hosts.
With ``--dry-run`` it hands over to the multi-pod dry run for the requested
arch/shape/sync.

  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-4b --dry-run
  PYTHONPATH=src python -m repro.launch.train --arch h2o-danube-1.8b \
      --reduced --steps 100 --sync efbv
"""
import argparse
import os
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--sync", default="dense",
                    choices=["dense", "efbv", "ef21", "diana", "hier", "local"])
    ap.add_argument("--compressor", default="qsgd")
    ap.add_argument("--dry-run", action="store_true",
                    help="lower+compile on the production mesh instead of running")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced config on local devices")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()

    if args.dry_run:
        # the dry-run module must own the interpreter from the first import
        os.execv(sys.executable, [
            sys.executable, "-m", "repro.launch.dryrun",
            "--arch", args.arch, "--shape", args.shape,
            "--multi-pod", "multi" if args.multi_pod else "single",
            "--sync", args.sync, "--compressor", args.compressor,
        ])

    import jax
    from repro.configs import get_config
    from repro.configs.base import SyncConfig, TrainConfig
    from repro.data.synthetic import SyntheticLMDataset, lm_batch_iterator
    from repro.launch.mesh import make_host_mesh, make_mesh
    from repro.training.loop import train
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    tc = TrainConfig(model=cfg, seq_len=args.seq, global_batch=args.batch,
                     lr=3e-3, warmup_steps=10, total_steps=args.steps,
                     sync=SyncConfig(mode=args.sync, compressor=args.compressor))
    ds = SyntheticLMDataset(vocab_size=cfg.vocab_size, length=100000, seed=0)
    it = lm_batch_iterator(ds, args.batch, args.seq, seed=1)
    mesh = None
    if jax.device_count() > 1:
        # hier keeps one model replica per pod: its replicas need a pod axis
        mesh = (make_mesh((jax.device_count(), 1, 1), ("pod", "data", "model"))
                if args.sync == "hier" else make_host_mesh())
    n_groups = 2 if args.sync != "dense" else 1
    train(cfg, tc, it, n_groups=n_groups, n_pods=2, steps=args.steps,
          ckpt_path=args.ckpt, mesh=mesh)


if __name__ == "__main__":
    main()
