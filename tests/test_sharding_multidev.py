"""Multi-device sharding tests.

These spawn subprocesses with XLA_FORCE_HOST_PLATFORM_DEVICE_COUNT=8 so the
main pytest process keeps its single CPU device (per the harness contract).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(snippet: str) -> dict:
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys, json
        sys.path.insert(0, {root!r} + "/src")
        import jax, jax.numpy as jnp
        import numpy as np
        out = {{}}
    """).format(root=ROOT) + textwrap.dedent(snippet) + "\nprint(json.dumps(out))\n"
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_mini_dryrun_reduced_multipod():
    """A (2,2,2) 'multi-pod' mesh lowers+compiles train/decode for a reduced
    hybrid MoE arch — the same machinery the production dry-run uses."""
    out = _run("""
        from repro.configs import get_config
        from repro.configs.base import INPUT_SHAPES, InputShape
        from repro.launch import sharded as dr
        from repro.launch.mesh import make_mesh
        cfg = get_config("jamba-1.5-large-398b").reduced()
        import repro.configs.base as base
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        shape = InputShape("t", 32, 8, "train")
        low = dr.build_train_lowering(cfg, mesh, shape, grad_accum=2)
        comp = low.compile()
        out["train_ok"] = True
        out["collectives"] = "all-reduce" in comp.as_text() or "all-gather" in comp.as_text()
        shape_d = InputShape("d", 64, 8, "decode")
        low2 = dr.build_decode_lowering(cfg, mesh, shape_d)
        comp2 = low2.compile()
        out["decode_ok"] = True
    """)
    assert out["train_ok"] and out["decode_ok"]
    assert out["collectives"]


@pytest.mark.slow
def test_shardmap_moe_matches_scatter():
    """Expert-parallel shard_map MoE == scatter-dispatch MoE numerically."""
    out = _run("""
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.models import moe as moe_lib
        from repro.sharding.context import set_moe_specs
        mesh = make_mesh((2, 4), ("data", "model"))
        E, K, d, ff = 4, 2, 64, 128
        params = moe_lib.init_moe(jax.random.PRNGKey(0), d, ff, E, True, False,
                                  jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, d))
        kw = dict(num_experts=E, top_k=K, capacity_factor=float(E)/K,
                  act="silu", gated=True, shared_expert=False)
        y_ref, aux_ref = moe_lib.moe_ffn(params, x, **kw)
        with jax.set_mesh(mesh):
            y_sm, aux_sm = jax.jit(lambda p, x: moe_lib.moe_ffn_shardmap(
                p, x, mesh=mesh, data_axes=("data",), **kw))(params, x)
        err = float(jnp.max(jnp.abs(y_ref - y_sm)))
        scale = float(jnp.max(jnp.abs(y_ref)))
        out["rel_err"] = err / (scale + 1e-9)
        # aux is computed per data shard then averaged (standard per-device
        # load-balance); it differs from the global statistic by a Jensen gap
        out["aux_gap"] = abs(float(aux_ref) - float(aux_sm))
    """)
    assert out["rel_err"] < 1e-4, out
    assert out["aux_gap"] < 0.1, out


@pytest.mark.slow
def test_alltoall_moe_matches_scatter():
    """all-to-all expert dispatch == scatter-dispatch MoE (exact: same
    deterministic routing, same drop-free capacity)."""
    out = _run("""
        from repro.launch.mesh import make_mesh
        from repro.models import moe as moe_lib
        mesh = make_mesh((2, 4), ("data", "model"))
        E, K, d, ff = 4, 2, 64, 128
        params = moe_lib.init_moe(jax.random.PRNGKey(0), d, ff, E, True, False,
                                  jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, d))
        kw = dict(num_experts=E, top_k=K, capacity_factor=float(E)/K,
                  act="silu", gated=True, shared_expert=False)
        y_ref, _ = moe_lib.moe_ffn(params, x, **kw)
        with jax.set_mesh(mesh):
            y, _ = jax.jit(lambda p, x: moe_lib.moe_ffn_alltoall(
                p, x, mesh=mesh, data_axes=("data",), **kw))(params, x)
        out["rel_err"] = float(jnp.linalg.norm(y - y_ref) /
                               (jnp.linalg.norm(y_ref) + 1e-9))
    """)
    assert out["rel_err"] < 1e-5, out


@pytest.mark.slow
def test_efbv_sync_mode_lowered_multidev():
    out = _run("""
        from repro.configs import get_config
        from repro.configs.base import InputShape
        from repro.launch import sharded as dr
        from repro.launch.mesh import make_mesh
        cfg = get_config("qwen1.5-4b").reduced()
        mesh = make_mesh((4, 2), ("data", "model"))
        shape = InputShape("t", 32, 8, "train")
        low = dr.build_train_lowering(cfg, mesh, shape, sync_mode="efbv",
                                      compressor="qsgd")
        comp = low.compile()
        out["ok"] = True
    """)
    assert out["ok"]


@pytest.mark.slow
def test_sharded_efbv_kernel_train_matches_vmapped():
    """training.loop.train on a (data=4, model=1) mesh with the Pallas
    compressor (each device compresses its own group) gives the losses of
    the one-device step with the 4 groups vmapped; its state spans 4."""
    out = _run("""
        from repro.configs import get_config
        from repro.configs.base import SyncConfig, TrainConfig
        from repro.data.synthetic import SyntheticLMDataset, lm_batch_iterator
        from repro.launch.mesh import make_mesh
        from repro.training.loop import train
        cfg = get_config("h2o-danube-1.8b").reduced()
        tc = TrainConfig(model=cfg, seq_len=32, global_batch=4, warmup_steps=1,
                         sync=SyncConfig(mode="efbv", compressor="qsgd_kernel",
                                         bucket_size=0))
        ds = SyntheticLMDataset(vocab_size=cfg.vocab_size, length=5000, seed=0)
        mesh = make_mesh((4, 1), ("data", "model"), devices=jax.devices()[:4])
        runs = {}
        for name, kw in (("mesh", dict(mesh=mesh)), ("vmap", dict(n_groups=4))):
            state, hist = train(cfg, tc, lm_batch_iterator(ds, 4, 32, seed=1),
                                steps=3, **kw)
            runs[name] = [h["loss"] for h in hist]
            out[name + "_devices"] = sorted({len(a.sharding.device_set) for a
                                             in jax.tree_util.tree_leaves(state)})
        out.update(runs)
    """)
    assert out["mesh_devices"] == [4] and out["vmap_devices"] == [1], out
    np.testing.assert_allclose(out["mesh"], out["vmap"], rtol=1e-5)


def test_param_specs_rules():
    """Rules engine: spot-check specs (a shape-only fake mesh suffices —
    param_specs consults only mesh.shape / axis_names)."""
    from types import SimpleNamespace

    import jax
    from repro.configs import get_config
    from repro.models import init_params
    from repro.sharding.rules import param_specs

    prod = SimpleNamespace(shape={"data": 16, "model": 16},
                           axis_names=("data", "model"))
    cfg_full = get_config("dbrx-132b")
    params_full = jax.eval_shape(lambda k: init_params(k, cfg_full),
                                 jax.random.PRNGKey(0))
    specs_full = param_specs(params_full, prod, extra_leading=1,
                             fsdp_axes=("data",))
    flat_full = {jax.tree_util.keystr(k): tuple(v)
                 for k, v in jax.tree_util.tree_flatten_with_path(specs_full)[0]}
    moe_win = [v for k, v in flat_full.items() if "moe" in k and "w_in" in k]
    assert moe_win and all(v[1] == "model" for v in moe_win)  # (stack, E, d, ff)
    assert all(v[2] in ("data", ("data",)) for v in moe_win)  # fsdp on d
    attn_wq = [v for k, v in flat_full.items() if "attn" in k and "wq" in k]
    assert attn_wq and all(v[-1] == "model" for v in attn_wq)
    embeds = [v for k, v in flat_full.items() if "embed" in k and "tok" in k]
    assert embeds and all(v[-1] == "model" and v[-2] is None for v in embeds)
