"""Compile the Pallas kernels of the main path for a described TPU v5e chip.

Nothing runs: each wrapper in ``kernels/ops.py`` is lowered and compiled by
the TPU compiler for a chip that is described, not attached, at the size of
one h2o-danube-1.8b MLP leaf (d_model x d_ff = 2560 x 6912).  A kernel the
chip's compiler would refuse (misaligned VMEM slices, unsupported
reductions, too much VMEM) fails here, and every case asserts that the
compiled program really holds the kernel (``tpu_custom_call``) rather than an
interpreted fallback.  The mode comes from the same backend helper the
program uses, asked for 'tpu'.

The topology is described only inside the module fixture: loading the TPU
library at import or collection time would make test workers disagree on
what exists.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.backend import interpret_for

D_IN, D_OUT = 2560, 6912    # h2o-danube-1.8b d_model x d_ff
D = D_IN * D_OUT
CALIB_TOKENS = 128          # calibration rows for the fused prune scores


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache

    # compiles for a described chip are written to a persistent cache but
    # cannot be read back without the chip; keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            from jax.experimental import topologies

            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            jax.config.update("jax_enable_compilation_cache", was_on)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _case(name, s):
    """(jitted wrapper, abstract args, static kwargs) for one kernel."""
    key = _sds((2,), jnp.uint32, s)
    w = _sds((D_IN, D_OUT), jnp.float32, s)
    if name == "quantize_dequantize":
        return ops.quantize_dequantize, (_sds((D,), jnp.float32, s), key), {}
    if name == "quantize_pack":
        return ops.quantize_pack, (_sds((D,), jnp.float32, s), key), {}
    if name == "stream_quantize_pack":
        return ops.stream_quantize_pack, (_sds((D,), jnp.float32, s), key), {}
    if name == "unpack_dequantize":
        rows = D // 512
        return ops.unpack_dequantize, (_sds((rows, 512), jnp.int8, s),
                                       _sds((rows, 1), jnp.float32, s)), {"d": D}
    if name == "pack_bits":
        return ops.pack_bits, (_sds((D,), jnp.bool_, s),), {}
    if name == "unpack_bits":
        return ops.unpack_bits, (_sds((-(-D // 32),), jnp.uint32, s),), {"d": D}
    if name == "prune_nm":
        return ops.prune_nm, (w, w), {}
    mode = name.removeprefix("prune_scored_")
    x = _sds((CALIB_TOKENS, D_IN), jnp.float32, s)
    return ops.prune_scored, (w, x), {"mode": mode}


KERNELS = ("quantize_dequantize", "quantize_pack", "stream_quantize_pack",
           "unpack_dequantize", "pack_bits", "unpack_bits", "prune_nm",
           "prune_scored_wanda", "prune_scored_ria", "prune_scored_symwanda")


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args, kw = _case(name, one_chip)
    compiled = fn.lower(*args, interpret=interpret_for("tpu"), **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_efbv_sync_with_kernel_compressor_compiles_on_mesh(topo):
    """The compressed sync of a (data=4, model=1) mesh, with the Pallas
    compressor: GSPMD cannot partition a Mosaic kernel, so each chip must
    compress its own worker group (core.distributed._map_groups)."""
    from repro.configs.base import SyncConfig
    from repro.core import distributed as dist
    from repro.core.compressors import qsgd_kernel

    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    G = 4
    sync = SyncConfig(mode="efbv", compressor="qsgd_kernel", bucket_size=0)
    c = qsgd_kernel(interpret=interpret_for("tpu"))
    lam, nu = dist.sync_params(sync, G)
    params = {"w_in": jax.ShapeDtypeStruct((D_IN, D_OUT), jnp.bfloat16),
              "w_out": jax.ShapeDtypeStruct((D_OUT, D_IN), jnp.bfloat16)}
    state = jax.eval_shape(lambda p: dist.sync_state_init(p, G, sync), params)

    def on(tree, spec):
        return jax.tree_util.tree_map(
            lambda a: _sds(a.shape, a.dtype, NamedSharding(mesh, spec)), tree)

    grads_g = on({k: jax.ShapeDtypeStruct((G,) + v.shape, v.dtype)
                  for k, v in params.items()}, P("data"))
    state = state._replace(h=on(state.h, P("data")), h_bar=on(state.h_bar, P()),
                           step=on(state.step, P()))
    key = _sds((2,), jnp.uint32, NamedSharding(mesh, P()))
    with jax.set_mesh(mesh):
        lowered = jax.jit(lambda k, g, s: dist.efbv_sync(
            k, g, s, c, lam, nu, bucket_size=0)).lower(key, grads_g, state)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
