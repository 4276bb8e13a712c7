"""Sharded step builders: train, prefill and decode lowered under a mesh.

Given a mesh and a config, each builder derives the parameter, optimizer,
sync-state, batch and cache shardings from ``sharding/rules.py``, installs
the trace-time sharding hooks (activation, gradient and MoE specs) for the
length of the lowering only, and lowers the step from ``training/steps.py``
against abstract shapes.  The dry run compiles the result for the
production mesh; ``training.loop.train(mesh=...)`` compiles it for the
devices at hand and runs it.  Importing this module touches no device and
sets no environment variable.
"""
from __future__ import annotations

import contextlib
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import InputShape, SyncConfig, TrainConfig
from repro.launch.specs import input_specs
from repro.models import init_params
from repro.sharding import rules
from repro.sharding.context import set_grad_specs, set_moe_specs
from repro.sharding.rules import (
    batch_specs, cache_pspecs, data_axes, maybe_axis, param_specs)
from repro.training.steps import (
    init_train_state, make_decode_step, make_prefill_step, make_train_step)


def _sharding(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


@contextlib.contextmanager
def _trace_hooks(act=None, grads=None, moe=None):
    """Install the trace-time sharding hooks for one lowering, then clear
    them, so a later unsharded trace in the same process sees none."""
    from repro.models.transformer import set_activation_sharding

    set_activation_sharding(act)
    set_grad_specs(grads)
    set_moe_specs(moe)
    try:
        yield
    finally:
        set_activation_sharding(None)
        set_grad_specs(None)
        set_moe_specs(None)


def _moe_specs(cfg, mesh, impl: str, **extra):
    if cfg.moe is None:
        return None
    from repro.sharding.context import get_moe_gather_quant, get_moe_impl_override

    return {"impl": get_moe_impl_override() or impl, "mesh": mesh,
            "data_axes": data_axes(mesh),
            "gather_quant": get_moe_gather_quant(), **extra}


def mesh_groups(mesh) -> tuple:
    """(n_groups, n_pods): data-parallel worker groups are the product of the
    data axes; pods are the 'pod' axis (1 without one)."""
    n_groups = 1
    for a in data_axes(mesh):
        n_groups *= mesh.shape[a]
    return n_groups, mesh.shape.get("pod", 1)


def auto_grad_accum(cfg, shape, n_data: int, width_shards: int = 16) -> int:
    """Microbatch count so remat residuals + logits fit HBM: scale with the
    per-device token load and residual width."""
    local_batch = max(1, shape.global_batch // n_data)
    resid_gb = (cfg.num_layers * local_batch * shape.seq_len * cfg.d_model * 2
                / width_shards / 1e9)  # model-sharded bf16 stack
    accum = 1
    while resid_gb / accum > 1.0 and accum < local_batch:
        accum *= 2
    return accum


class ShardedTrainStep(NamedTuple):
    """A train step lowered for a mesh, with the shardings its inputs need."""
    lowered: Any            # jax.stages.Lowered of step(state, batch)
    state_shardings: Any    # TrainState of NamedSharding
    batch_shardings: Any    # dict of NamedSharding
    n_groups: int
    n_pods: int


def lower_train_step(cfg, mesh, tc: TrainConfig) -> ShardedTrainStep:
    """Lower ``make_train_step`` for ``mesh``, the train state donated.

    The batch is ``tc.global_batch`` sequences of ``tc.seq_len`` tokens; the
    worker groups of the compressed sync modes are the mesh's data axes."""
    daxes = data_axes(mesh)
    n_groups, n_pods = mesh_groups(mesh)
    mode = tc.sync.mode

    params_abs = jax.eval_shape(lambda k: init_params(k, cfg),
                                jax.random.PRNGKey(0))
    state_abs = jax.eval_shape(
        lambda: init_train_state(jax.random.PRNGKey(0),
                                 jax.tree_util.tree_map(
                                     lambda s: jnp.zeros(s.shape, s.dtype),
                                     params_abs),
                                 tc, n_groups, n_pods))

    dax = daxes if len(daxes) > 1 else daxes[0]
    if mode in ("hier", "local"):
        rep_ax = ("pod",) if mode == "hier" else dax
        fsdp = ("data",) if mode == "hier" else None
        pspecs = param_specs(state_abs.params, mesh, extra_leading=2,
                             replica_axes=rep_ax if not isinstance(rep_ax, tuple) or len(rep_ax) > 1 else rep_ax[0],
                             fsdp_axes=fsdp)
        act = NamedSharding(mesh, P("data", None, "model") if mode == "hier"
                            else P(None, None, "model"))
    elif mode == "dense":
        fsdp_ax = daxes + ("model",) if rules.NO_TP else daxes
        pspecs = param_specs(state_abs.params, mesh, extra_leading=1,
                             fsdp_axes=fsdp_ax)
        # NO_TP: pure data parallel, batch over ALL axes, no model-dim sharding
        act = NamedSharding(mesh, P(daxes + ("model",), None, None) if rules.NO_TP
                            else P(dax, None, "model"))
    else:  # efbv family: per-group grads via vmap — batch dim is mapped
        pspecs = param_specs(state_abs.params, mesh, extra_leading=1,
                             fsdp_axes=daxes)
        act = NamedSharding(mesh, P(None, None, "model"))
    ospecs_mu = jax.tree_util.tree_map(lambda p, s: P(*s),
                                       state_abs.opt_state.mu, pspecs)
    opt_specs = type(state_abs.opt_state)(step=P(), mu=ospecs_mu, nu=ospecs_mu)
    if state_abs.sync_state is None:
        sync_specs = None
    else:
        if mode in ("efbv", "ef21", "diana"):
            # h_i per worker group: leading dim over (pod, data); param dims
            # keep tensor-parallel sharding only (no fsdp — the group axis
            # already consumes the data axes)
            h_base = param_specs(state_abs.params, mesh, extra_leading=1)
            h_specs = jax.tree_util.tree_map(lambda s: P(dax, *tuple(s)), h_base)
            hb_specs = jax.tree_util.tree_map(lambda s: P(*s), pspecs)
        else:
            h_specs = ()
            # h_bar: no replica dim — param spec minus the leading replica axis
            hb_specs = jax.tree_util.tree_map(lambda s: P(*tuple(s)[1:]), pspecs)
        sync_specs = type(state_abs.sync_state)(h=h_specs, h_bar=hb_specs,
                                                step=P())
    state_specs = type(state_abs)(params=pspecs, opt_state=opt_specs,
                                  sync_state=sync_specs, key=P())

    batch_abs = input_specs(cfg, InputShape("train", tc.seq_len,
                                            tc.global_batch, "train"))
    state_sh = _sharding(mesh, state_specs)
    batch_sh = _sharding(mesh, batch_specs(batch_abs, mesh))
    metrics_sh = _sharding(mesh, {"loss": P(), "ce": P(), "grad_norm": P()})

    # pin gradient sharding to the param sharding so FSDP backward grads are
    # reduce-scattered instead of kept replicated through the f32 update
    grads = _sharding(mesh, pspecs) if mode == "dense" else None
    # shard_map expert parallelism for train/prefill (scatter dispatch is
    # unpartitionable); efbv's vmap-over-groups keeps the scatter path
    moe = _moe_specs(cfg, mesh, "shardmap" if mode in ("dense", "hier") else "scatter",
                     tokens=P(None, "model"), expanded=P(None, "model"),
                     buf=P("model", None, None))
    jitted = jax.jit(make_train_step(cfg, tc, n_groups, n_pods),
                     in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, metrics_sh),
                     donate_argnums=0)
    with _trace_hooks(act, grads, moe), jax.set_mesh(mesh):
        lowered = jitted.lower(state_abs, batch_abs)
    return ShardedTrainStep(lowered, state_sh, batch_sh, n_groups, n_pods)


def build_train_lowering(cfg, mesh, shape, sync_mode="dense", compressor="qsgd",
                         sync_period=4, remat="full", grad_accum=None):
    """The dry run's train step for ``shape`` on ``mesh``: lowered, not run."""
    if grad_accum is None:
        n_groups, _ = mesh_groups(mesh)
        if sync_mode != "dense":
            grad_accum = 1
        elif rules.NO_TP:
            grad_accum = auto_grad_accum(
                cfg, shape, n_groups * mesh.shape["model"], width_shards=1)
        else:
            grad_accum = auto_grad_accum(cfg, shape, n_groups)
    tc = TrainConfig(model=cfg, seq_len=shape.seq_len, global_batch=shape.global_batch,
                     remat=remat, grad_accum=grad_accum,
                     sync=SyncConfig(mode=sync_mode, compressor=compressor,
                                     sync_period=sync_period))
    return lower_train_step(cfg, mesh, tc).lowered


def _serving_fsdp(cfg, mesh):
    """FSDP params for serving only when tensor-parallel-only weights would
    not fit HBM (weight-gathered inference for the >60B archs)."""
    tp_bytes = cfg.param_count() * 2 / mesh.shape["model"]
    return data_axes(mesh) if tp_bytes > 8e9 else None


def build_prefill_lowering(cfg, mesh, shape, remat="full"):
    daxes = data_axes(mesh)
    dax = daxes if len(daxes) > 1 else daxes[0]
    act = NamedSharding(mesh, P(dax, None, "model"))
    moe = _moe_specs(cfg, mesh, "shardmap")
    params_abs = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    pspecs = param_specs(params_abs, mesh, extra_leading=1,
                         fsdp_axes=_serving_fsdp(cfg, mesh))
    batch_abs = input_specs(cfg, shape)
    bspecs = batch_specs(batch_abs, mesh)
    logits_spec = P(maybe_axis(shape.global_batch, dax, mesh), None,
                    maybe_axis(cfg.padded_vocab(), "model", mesh))
    with _trace_hooks(act, None, moe), jax.set_mesh(mesh):
        cache_abs = jax.eval_shape(
            lambda p, b: make_prefill_step(cfg, remat)(p, b)[1],
            params_abs, batch_abs)
        cspecs = cache_pspecs(cache_abs, mesh)
        jitted = jax.jit(
            make_prefill_step(cfg, remat),
            in_shardings=(_sharding(mesh, pspecs), _sharding(mesh, bspecs)),
            out_shardings=(NamedSharding(mesh, logits_spec),
                           _sharding(mesh, cspecs)),
        )
        return jitted.lower(params_abs, batch_abs)


def build_decode_lowering(cfg, mesh, shape):
    # decode keeps the scatter dispatch (tiny T): no activation or MoE specs
    params_abs = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    pspecs = param_specs(params_abs, mesh, extra_leading=1,
                         fsdp_axes=_serving_fsdp(cfg, mesh))
    specs = input_specs(cfg, shape)
    token_abs, cache_abs = specs["token"], specs["cache"]
    tspec = batch_specs({"t": token_abs}, mesh)["t"]
    cspecs = cache_pspecs(cache_abs, mesh)
    logits_spec = P(tuple(tspec)[0], None,
                    maybe_axis(cfg.padded_vocab(), "model", mesh))

    jitted = jax.jit(
        make_decode_step(cfg),
        in_shardings=(_sharding(mesh, pspecs), NamedSharding(mesh, tspec),
                      _sharding(mesh, cspecs)),
        out_shardings=(NamedSharding(mesh, logits_spec), _sharding(mesh, cspecs)),
    )
    with _trace_hooks(), jax.set_mesh(mesh):
        return jitted.lower(params_abs, token_abs, cache_abs)
