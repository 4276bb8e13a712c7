"""Compile the Pallas kernels of the main path for a described TPU v5e chip.

Nothing runs: each wrapper in ``kernels/ops.py`` is lowered and compiled by
the TPU compiler for a chip that is described, not attached, at the size of
one h2o-danube-1.8b MLP leaf (d_model x d_ff = 2560 x 6912; the Mamba state
step at mamba2-2.7b's widths).  A kernel the
chip's compiler would refuse (misaligned VMEM slices, unsupported
reductions, too much VMEM) fails here, and every case asserts that the
compiled program really holds the kernel (``tpu_custom_call``) rather than an
interpreted fallback.  The mode comes from the same backend helper the
program uses, asked for 'tpu'.

The serving decode step is compiled the same way, at Qwen1.5-4B's widths,
and its optimized HLO is read for copies of the KV cache: the step must
write its token into the stacked cache in place.  granite-4.0-h-micro's
decode, as the batcher builds it, must alias its whole cache when the
batcher donates it, copy no SSM state then, and step each Mamba layer's
state in one kernel pass.

The topology is described only inside the module fixture: loading the TPU
library at import or collection time would make test workers disagree on
what exists.
"""
import re
from functools import partial

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
    if name == "ssm_state_step":
        # mamba2-2.7b's widths (80 heads of 64, d_state 128): blocks of 40
        # heads; a stack of 4 layers at 8 slots
        p_, b_, h_, hd, n = 4, 8, 80, 64, 128
        return ops.ssm_state_step, (
            _sds((p_, b_, h_, hd, n), jnp.float32, s), _sds((), jnp.int32, s),
            _sds((b_, h_), jnp.float32, s), _sds((b_, h_), jnp.float32, s),
            _sds((b_, h_, hd), jnp.float32, s), _sds((b_, 1, n), jnp.float32, s),
            _sds((b_, 1, n), jnp.float32, s)), {}
    mode = name.removeprefix("prune_scored_")
    x = _sds((CALIB_TOKENS, D_IN), jnp.float32, s)
    return ops.prune_scored, (w, x), {"mode": mode}


KERNELS = ("quantize_dequantize", "quantize_pack", "stream_quantize_pack",
           "unpack_dequantize", "pack_bits", "unpack_bits", "prune_nm",
           "prune_scored_wanda", "prune_scored_ria", "prune_scored_symwanda",
           "ssm_state_step")


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


# -- the decode program's structure -------------------------------------------
# Qwen1.5-4B's widths (d_model 2560, 20 heads of 128 with QKV bias, bf16) at
# 4 layers, 8 slots and a 768-position cache: one layer's K (or V) cache is
# 8 x 768 x 20 x 128 elements, the stack of them 4 times that.
DECODE_LAYERS, SLOTS, CACHE_LEN = 4, 8, 768

_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) ", re.M)
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\((.*)$")


def _computations(hlo: str) -> dict:
    """{name: instruction lines} of every computation of an HLO module."""
    out, name = {}, None
    for line in hlo.splitlines():
        m = _COMPUTATION.match(line)
        if m and line.rstrip().endswith("{"):
            name = m.group(1)
            out[name] = []
        elif name is not None and line.startswith(" "):
            out[name].append(line)
    return out


def _instructions(lines):
    """(name, element count, opcode, rest of line) of each array-valued
    instruction."""
    for line in lines:
        m = _INSTRUCTION.match(line)
        if m:
            dims = [int(d) for d in m.group(2).split(",") if d]
            yield m.group(1), int(np.prod(dims)), m.group(3), m.group(4)


def _copies(comps: dict, opcode: str, rest: str) -> bool:
    """Whether an instruction is a copy: a ``copy``, or a fusion that holds
    one (a relayout that XLA fused with the op it feeds)."""
    if opcode == "copy":
        return True
    m = re.search(r"calls=%(\S+?)[,\s]", rest)
    return (opcode == "fusion" and m is not None and
            any(op in ("copy", "transpose")
                for _, _, op, _ in _instructions(comps[m.group(1)])))


@pytest.fixture(scope="module")
def decode_hlo(one_chip):
    from dataclasses import replace

    from repro import models
    from repro.configs import get_config

    cfg = replace(get_config("qwen1.5-4b"), num_layers=DECODE_LAYERS)

    def on(tree):
        return jax.tree_util.tree_map(
            lambda a: _sds(a.shape, a.dtype, one_chip), tree)

    params = jax.eval_shape(lambda k: models.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    cache = models.cache_specs(cfg, SLOTS, CACHE_LEN)
    token = _sds((SLOTS, 1), jnp.int32, one_chip)
    compiled = jax.jit(lambda p, t, c: models.decode_step(p, cfg, t, c)).lower(
        on(params), token, on(cache)).compile()
    layer = SLOTS * CACHE_LEN * cfg.num_kv_heads * cfg.head_dim
    return compiled.as_text(), layer


def test_decode_writes_the_cache_in_place(decode_hlo):
    """The layer loop copies no layer's K/V cache out of the stack and writes
    no new stack: outside fused computations no instruction produces a
    layer's whole cache, and the only copies of the stacked K and V are the
    one each that an undonated argument needs, outside the loop."""
    hlo, layer = decode_hlo
    comps = _computations(hlo)
    fused = set(re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", hlo))
    bodies = set(re.findall(r"body=%([\w.\-]+)", hlo))
    assert bodies, "the layer scan is no longer a loop"
    layer_sized, stack_copies = [], []
    for comp, lines in comps.items():
        if comp in fused:
            continue
        for name, n, opcode, rest in _instructions(lines):
            if n == layer:
                layer_sized.append(name)
            if n == DECODE_LAYERS * layer and _copies(comps, opcode, rest):
                stack_copies.append((name, comp in bodies))
    assert not layer_sized, layer_sized
    assert len(stack_copies) <= 2, stack_copies
    assert not any(in_loop for _, in_loop in stack_copies), stack_copies


# granite-4.0-h-micro whole (36 Mamba-2 layers, 4 attention) at 32 slots and
# a 1536-position cache, as the hybrid serving cell runs it.  The program
# asks the backend whether kernels compile; the test answers as a TPU does,
# so the decode takes the Mamba state-step kernel as it does on the chip.
HYBRID_SLOTS, HYBRID_CACHE_LEN = 32, 1536
_hybrid_decodes: dict = {}


def _hybrid_decode(one_chip, donate):
    """(compiled decode program, cache shapes, ServeStats) of
    ``ContinuousBatcher``'s granite decode, compiled once per arm."""
    if donate in _hybrid_decodes:
        return _hybrid_decodes[donate]
    from repro import models
    from repro.configs import get_config
    from repro.kernels import backend
    from repro.training.serving import ContinuousBatcher

    cfg = get_config("granite-4.0-h-micro")
    params = jax.eval_shape(lambda k: models.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    cache = models.cache_specs(cfg, HYBRID_SLOTS, HYBRID_CACHE_LEN)
    on = partial(jax.tree_util.tree_map,
                 lambda a: _sds(a.shape, a.dtype, one_chip))
    batcher = ContinuousBatcher(cfg, None, n_slots=HYBRID_SLOTS,
                                max_len=HYBRID_CACHE_LEN, donate_cache=donate)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(backend, "interpret_for",
                   lambda backend_name=None: interpret_for("tpu"))
        compiled = batcher._decode.lower(
            on(params), _sds((HYBRID_SLOTS, 1), jnp.int32, one_chip),
            on(cache)).compile()
    _hybrid_decodes[donate] = compiled, cache, batcher.stats
    return _hybrid_decodes[donate]


def _ssm_states(cache):
    return [a for path, a in
            jax.tree_util.tree_flatten_with_path(cache["layers"])[0]
            if path[-1].key == "ssm"]


@pytest.mark.parametrize("donate", [False, True])
def test_donated_decode_writes_the_ssm_state_in_place(one_chip, donate):
    """``ContinuousBatcher(donate_cache=True)``'s decode program aliases the
    whole cache to its output and copies no stacked SSM state; undonated,
    each stack is copied before the step writes it."""
    compiled, cache, _ = _hybrid_decode(one_chip, donate)
    states = _ssm_states(cache)
    state_copies = [name for lines in _computations(compiled.as_text()).values()
                    for name, n, opcode, _ in _instructions(lines)
                    if opcode == "copy" and n == states[0].size]
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(cache["layers"]))
    aliased = compiled.memory_analysis().alias_size_in_bytes
    if donate:
        assert aliased >= cache_bytes and not state_copies, state_copies
    else:
        assert aliased == 0 and len(state_copies) == len(states)


_CUSTOM_CALL = re.compile(r"^\s*(?:ROOT )?%(\S+) = .* custom-call\(")
_FUSION = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) fusion\(")


def test_decode_steps_each_ssm_state_in_one_pass(one_chip):
    """The donated decode runs each Mamba layer's state step as one kernel
    call on the stacked state (one a Mamba position of the period, in the
    layer loop): no fusion writes a layer's state to a temporary, no
    dynamic-update-slice writes one into its stack, and the whole cache
    is still aliased.  The batcher counts the 36 layers the kernel serves."""
    compiled, cache, stats = _hybrid_decode(one_chip, True)
    hlo = compiled.as_text()
    comps = _computations(hlo)
    bodies = set(re.findall(r"body=%([\w.\-]+)", hlo))
    states = _ssm_states(cache)
    layer_state = "f32[%s]" % ",".join(map(str, states[0].shape[1:]))
    kernels = [m.group(1) for comp in bodies for line in comps[comp]
               if (m := _CUSTOM_CALL.match(line)) and "tpu_custom_call" in line
               and m.group(1).startswith("ssm_state_step")]
    assert len(kernels) == len(states), kernels
    temporaries = [m.group(1) for lines in comps.values() for line in lines
                   if (m := _FUSION.match(line)) and layer_state in m.group(2)]
    assert not temporaries, temporaries
    state_writes = [name for lines in comps.values()
                    for name, n, opcode, _ in _instructions(lines)
                    if opcode == "dynamic-update-slice" and n == states[0].size]
    assert not state_writes, state_writes
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(cache["layers"]))
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes
    assert stats.ssm_fused_layers == 36
