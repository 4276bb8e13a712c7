"""Host-side training loop: data feed, jit'd step, metrics, checkpoints."""
from __future__ import annotations

from typing import Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, TrainConfig
from repro.models import init_params
from repro.obs import trace as obs_trace
from repro.training.checkpoint import save_checkpoint
from repro.training.steps import TrainState, init_train_state, make_train_step
from repro.utils.logging import get_logger, log_kv

log = get_logger("train")


def _fault_model(tc: TrainConfig, n_groups: int, n_pods: int):
    """FaultModel bound to the sync cascade, or None (faults off / dense).

    Tree hier mode binds to the configured tree topology; flat hier/local
    binds to the depth-1 tree whose single ``inter`` level fans every replica
    group into the server, so the survivor mask is one per-group vector.
    """
    faults = getattr(tc.sync, "faults", None)
    if faults is None or not faults.enabled():
        return None
    if tc.sync.mode not in ("hier", "local"):
        return None
    from repro.faults import FaultModel

    if tc.sync.mode == "hier" and tc.sync.levels:
        from repro.comm.tree import get_tree_topology

        tree = get_tree_topology(tc.sync.topology)
    else:
        from repro.comm.topology import Link, get_topology
        from repro.comm.tree import TreeLevel, TreeTopology

        G = n_pods if tc.sync.mode == "hier" else n_groups
        try:
            link = get_topology(tc.sync.topology).inter
        except Exception:
            link = Link(gbps=1.0, latency_us=1000.0)
        tree = TreeTopology(f"{tc.sync.topology}-flat",
                            (TreeLevel("inter", G, link),))
    return FaultModel(faults, tree)


def train(cfg: ModelConfig, tc: TrainConfig, batches: Iterator[dict],
          n_groups: int = 1, n_pods: int = 1, steps: Optional[int] = None,
          ckpt_path: Optional[str] = None, log_every: int = 10, mesh=None):
    """Training entry of the launcher (launch/train.py) and the examples.

    Without ``mesh`` the step runs on the default device, with ``n_groups``
    worker groups vmapped inside it.  With ``mesh`` the step is the sharded
    one from launch/sharded.py, its worker groups are the mesh's data axes,
    and the state lives sharded over the mesh.  The state is donated to each
    step: only the returned state is valid afterwards."""
    steps = steps or tc.total_steps
    fault_model = _fault_model(tc, n_groups, n_pods)
    state_shardings = batch_shardings = None
    if mesh is None:
        step_fn = jax.jit(make_train_step(cfg, tc, n_groups, n_pods),
                          donate_argnums=0)
    else:
        if fault_model is not None:
            raise ValueError("fault injection runs on the single-device step; "
                             "train without a mesh")
        from repro.launch.sharded import lower_train_step

        sharded = lower_train_step(cfg, mesh, tc)
        n_groups, n_pods = sharded.n_groups, sharded.n_pods
        state_shardings = sharded.state_shardings
        batch_shardings = sharded.batch_shardings
        step_fn = sharded.lowered.compile()

    def _init(key):
        key, kinit = jax.random.split(key)
        return init_train_state(key, init_params(kinit, cfg), tc, n_groups,
                                n_pods)

    state = jax.jit(_init, out_shardings=state_shardings)(
        jax.random.PRNGKey(tc.seed))

    if tc.sync.mode != "dense":
        from repro.core.distributed import round_comm

        cost = round_comm(tc.sync, cfg.param_count())
        dense = 4.0 * cfg.param_count()
        stream = (f" streamed over {cost.tile_bytes >> 10} KB tiles "
                  f"(serial {cost.serial_time_s * 1e3:.2f} ms, "
                  f"{cost.stream_speedup:.2f}x)"
                  if cost.tile_bytes else " (monolithic codec)")
        log.info("sync=%s: %.3f MB/round on the slow links (%.1fx vs dense "
                 "fp32)%s, simulated %.2f ms/round on %s,%s",
                 tc.sync.mode, cost.inter_bytes / 1e6,
                 dense / max(cost.inter_bytes, 1e-9),
                 (f" + {cost.intra_bytes / 1e6:.1f} MB intra-pod"
                  if cost.intra_bytes else ""),
                 cost.time_s * 1e3, tc.sync.topology, stream)
        for lv in cost.levels:
            log.info("  level %-8s fanout %3d period %3d %-10s "
                     "%.3f MB/round  %.2f ms/round",
                     lv.name, lv.fanout, lv.period, lv.compressor,
                     lv.bytes_per_round / 1e6, lv.time_s * 1e3)
        if obs_trace.enabled():
            from repro.obs import registry

            registry.observe_round_cost(0, cost)

    fault_nbytes = None
    if fault_model is not None:
        log.info("fault injection on (seed=%d): degraded rounds aggregate "
                 "over deadline survivors; replayable from (seed, round)",
                 tc.sync.faults.seed)
        if (tc.sync.mode != "dense"
                and len(cost.levels) == len(fault_model.tree.levels)):
            # size each level's nominal message from the measured round cost
            # (bytes_per_round is amortized over the level period) so
            # straggler arrivals and deadline misses reflect real payloads,
            # not latency-only links
            fault_nbytes = [lv.bytes_per_round * lv.period
                            for lv in cost.levels]

    history = []
    t0 = obs_trace.wall_s()
    for step in range(steps):
        tracing = obs_trace.enabled()
        # round boundary: the span covers batch staging + step dispatch, but
        # never blocks on device values — the blocking fetch is its own span
        with obs_trace.span("round/step", round=step), \
                obs_trace.step_annotation(step):
            batch = next(batches)
            tokens = batch["tokens"]
            model_batch = {"tokens": jnp.asarray(tokens[:, :-1]),
                           "targets": jnp.asarray(tokens[:, 1:])}
            for k, v in batch.items():
                if k != "tokens":
                    model_batch[k] = jnp.asarray(v)
            if batch_shardings is not None:
                model_batch = jax.device_put(model_batch, batch_shardings)
            if fault_model is None:
                state, metrics = step_fn(state, model_batch)
            else:
                # deterministic per-round fault plan; dropped children sync
                # with zero weight and keep their local params this round
                plan = fault_model.round_plan(step,
                                              nbytes_by_level=fault_nbytes)
                masks = tuple(jnp.asarray(m) for m in plan.survivor_masks())
                state, metrics = step_fn(state, model_batch, masks)
        if fault_model is not None and tracing:
            from repro.obs import registry

            registry.observe_fault_plan(step, plan)
        # metrics stay on device (async dispatch): one jax.device_get per log
        # point instead of a blocking float(v) transfer per metric per step
        history.append(metrics)
        log_step = step % log_every == 0 or step == steps - 1
        if tracing or log_step:
            with obs_trace.span("round/blocking_fetch", round=step):
                fetched = jax.device_get(metrics)
            if tracing:
                from repro.obs import registry

                vals = {k: float(v) for k, v in fetched.items()}
                registry.observe_train_step(step, vals)
                log_kv(log, "round", step=step, **vals)
            if log_step:
                dt = obs_trace.wall_s() - t0
                log.info("step %4d loss %.4f grad_norm %.3f (%.2fs)",
                         step, float(fetched["loss"]),
                         float(fetched["grad_norm"]), dt)
    # one transfer drains every step's still-on-device metrics
    history = [{k: float(v) for k, v in h.items()}
               for h in jax.device_get(history)]
    if ckpt_path:
        save_checkpoint(ckpt_path, state.params, step=steps)
        log.info("saved checkpoint to %s", ckpt_path)
    return state, history
