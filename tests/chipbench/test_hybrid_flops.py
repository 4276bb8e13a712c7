"""The hybrid's operation and byte counts against arithmetic by hand at
the tiny size, and its two readers against arithmetic by hand on a made-up
trace (silent where they find nothing to read)."""
from __future__ import annotations

import pytest

from benchmarks.chip import harness, hybrid_flops
from benchmarks.chip.devtrace import Device, Event, Trace

from conftest import REPO
from hybrid_tiny import published, tiny_hybrid

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def tiny():
    # d 64, 4/2 heads of 16, ff 128, vocab 512; 9 Mamba layers of 8 heads of
    # 16 (d_inner 128), d_state 16, one group, conv 4, chunk 8; 1 attention
    return tiny_hybrid()


def test_parameters(tiny):
    # in_proj 64 x (128 z + 160 xBC + 8 dt), out_proj 128 x 64
    assert hybrid_flops.mamba_matmul_params(tiny) == 64 * 296 + 128 * 64 == 27136
    assert hybrid_flops.attention_matmul_params(tiny) == \
        2 * 64 * 64 + 2 * 64 * 32 == 12288
    assert hybrid_flops.mlp_params(tiny) == 3 * 64 * 128 == 24576
    assert hybrid_flops.matmul_params(tiny) == \
        9 * 27136 + 12288 + 10 * 24576 + 64 * 512 == 535040
    # bf16: mixers, conv w (4 x 160) and b, gated norm 128; MLPs and two
    # norms a layer; embedding and final norm.  float32: A_log, dt_bias, D
    bf16 = 9 * (27136 + 640 + 160 + 128) + 12288 + 10 * (24576 + 128) + 32768 + 64
    assert hybrid_flops.weight_bytes(tiny) == 2 * bf16 + 4 * 9 * 3 * 8 == 1090336


def test_state_and_cache(tiny):
    # per slot and Mamba layer: state 8 x 16 x 16 float32, conv tail 3 x 160 bf16
    assert hybrid_flops.ssm_state_bytes(tiny, 2) == 9 * 2 * (8192 + 960) == 164736
    # one attention layer: K and V, 2 heads of 16, bf16, 64 positions, 2 slots
    assert hybrid_flops.kv_cache_bytes(tiny, 2, 64) == 2 * 64 * 2 * 2 * 16 * 2 == 16384
    # published: 36 layers x 32 slots x (64 x 64 x 128 x 4 + 3 x 4352 x 2)
    assert hybrid_flops.ssm_state_bytes(published(), 32) == 2_446_000_128
    assert hybrid_flops.kv_cache_bytes(published(), 32, 1536) == 402_653_184


def test_prefill(tiny):
    # 13 tokens: a chunk of 8 (36 causal pairs) and one of 5 (15 pairs);
    # C B^T 2 x 16 a pair, its product with x 2 x 128 a pair, the chunk's
    # state and its readout 2 x 128 x 16 a token each; conv 2 x 4 x 160
    chunks = (2 * 16 * 36 + 2 * 128 * 36 + 4 * 2048 * 8
              + 2 * 16 * 15 + 2 * 128 * 15 + 4 * 2048 * 5)
    ssd = 9 * (chunks + 2 * 4 * 160 * 13)
    assert hybrid_flops.ssd_flops(tiny, 13) == ssd == 1240416
    attn = 4 * 4 * 16 * sum(range(1, 14))
    row = 2 * (535040 - 32768) * 13 + 2 * 64 * 512 + attn + ssd
    assert hybrid_flops.prefill_flops(tiny, 2, 13) == 2 * row == 28776640


def test_decode(tiny):
    # a state step: 4 x 8 x 16 x 16 and the conv 2 x 4 x 160, 9 layers
    step = 2 * 535040 + 9 * (4 * 2048 + 1280)
    assert hybrid_flops.decode_flops(tiny, [20, 21]) == \
        2 * step + 4 * 4 * 16 * (21 + 22) == 2321664
    # weights, 2 embedding rows, the state read and written, K/V of 21 and
    # 22 positions at 128 bytes each
    assert hybrid_flops.decode_bytes(tiny, [20, 21]) == \
        1090336 + 2 * 64 * 2 + 2 * 164736 + 43 * 128 == 1425568


def make_run(facts, trace):
    cell = harness.Cell(REPO, {}, {"name": "x", "chips": 1}, published(), {}, {})
    return harness.Run(cell, harness.Outcome({}, 0, 0, [], 0, facts), trace,
                       PEAKS, 1)


def serving_trace(prefill_ns, decode_ns, runs):
    """One chip: a prefill module, then ``runs`` decode modules, back to
    back from 0, in a window of 10 s."""
    mods = [Event("jit_prefill(1)", 0, prefill_ns)]
    t = prefill_ns
    for _ in range(runs):
        mods.append(Event("jit_decode(2)", t, t + decode_ns))
        t += decode_ns
    ops = [Event("%f = f32[8]{0} fusion(x)", e.start, e.end) for e in mods]
    return Trace([Device(0, ops, mods)], [], (0.0, 1e10))


FACTS = {"ssm_state_bytes": 2_446_000_128, "slots": 32, "prompt_len": 1024,
         "decode_positions": [[1100] * 32, [1101] * 32]}


def test_readers_by_hand():
    c = published()
    trace = serving_trace(2e9, 28e6, 100)
    roof = harness.load_reader(REPO, "hybrid_decode_roofline")
    need = (hybrid_flops.decode_bytes(c, [1100] * 32)
            + hybrid_flops.decode_bytes(c, [1101] * 32)) / 2
    assert roof(make_run(FACTS, trace)) == pytest.approx(
        100 * (need / 819e9) / 28e-3)
    mfu = harness.load_reader(REPO, "hybrid_prefill_mfu")
    assert mfu(make_run(FACTS, trace)) == pytest.approx(
        100 * hybrid_flops.prefill_flops(c, 32, 1024) / (2.0 * 197e12))


@pytest.mark.parametrize("name", ["hybrid_decode_roofline",
                                  "hybrid_prefill_mfu"])
def test_readers_silent(name):
    read = harness.load_reader(REPO, name)
    dense = {k: v for k, v in FACTS.items() if k != "ssm_state_bytes"}
    assert read(make_run(dense, serving_trace(2e9, 28e6, 5))) is None
    assert read(make_run(FACTS, Trace([Device(0, [], [])], [], (0.0, 1e9)))) is None
