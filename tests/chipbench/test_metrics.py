"""Each per-layer reader against arithmetic by hand, and silent (None)
where it finds nothing to read."""
from __future__ import annotations

import json

import pytest

from benchmarks.chip import devtrace, flops, harness
from benchmarks.chip.devtrace import Device, Event, Trace

from conftest import REPO

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def config(name):
    return json.loads((REPO / "benchmarks" / "chip" / "configs"
                       / f"{name}.json").read_text())


def make_run(config_name, facts, trace):
    cell = harness.Cell(REPO, {}, {"name": "x", "chips": 1},
                        config(config_name), {}, {})
    out = harness.Outcome({}, 0, 0, [], 0, facts)
    return harness.Run(cell, out, trace, PEAKS, 1)


def serving_trace(decode_ns, runs):
    """One chip: a prefill module, then ``runs`` decode modules of
    ``decode_ns`` each, back to back from 0, in a window of 1 s."""
    mods = [Event("jit__lambda(1)", 0, 2e8)]
    t = 2e8
    for _ in range(runs):
        mods.append(Event("jit__lambda(2)", t, t + decode_ns))
        t += decode_ns
    ops = [Event("%f = f32[8]{0} fusion(x)", e.start, e.end) for e in mods]
    return Trace([Device(0, ops, mods)], [], (0.0, 1e9))


def test_decode_roofline():
    read = harness.load_reader(REPO, "decode_roofline")
    c = config("qwen1.5-4b-serve")
    positions = [[600] * 8, [601] * 8]
    run = make_run("qwen1.5-4b-serve", {"decode_positions": positions},
                   serving_trace(25e6, 30))
    need = (flops.decode_bytes(c, [600] * 8) + flops.decode_bytes(c, [601] * 8)) / 2
    assert read(run) == pytest.approx(100 * (need / 819e9) / 25e-3)
    empty = make_run("qwen1.5-4b-serve", {"decode_positions": positions},
                     Trace([Device(0, [], [])], [], (0.0, 1e9)))
    assert read(empty) is None


def test_serve_mfu():
    read = harness.load_reader(REPO, "serve_mfu")
    c = config("qwen1.5-4b-serve")
    facts = {"decode_positions": [[600] * 8], "decode_steps": 1,
             "prefill_rows": 8, "prompt_len": 512, "window_s": 2.0}
    want = (flops.prefill_flops(c, 8, 512) + flops.decode_flops(c, [600] * 8))
    assert read(make_run("qwen1.5-4b-serve", facts, None)) == \
        pytest.approx(100 * want / (2.0 * 197e12))
    assert read(make_run("qwen1.5-4b-serve", {}, None)) is None


def test_train_step_mfu():
    read = harness.load_reader(REPO, "train_step_mfu")
    facts = {"tokens": 10 * 16384, "seq_len": 4096, "window_s": 11.2}
    want = 3_849_692_160 * 10 * 16384 / (11.2 * 197e12)
    assert read(make_run("danube-1.8b-train", facts, None)) == pytest.approx(100 * want)
    assert read(make_run("danube-1.8b-train", {"tokens": 0}, None)) is None


@pytest.mark.parametrize("name", ["device_idle_share.train",
                                  "device_idle_share.serve"])
def test_idle_share(name):
    read = harness.load_reader(REPO, name)
    trace = serving_trace(25e6, 30)          # busy 0.2 + 0.75 s of 1 s
    assert read(make_run("qwen1.5-4b-serve", {}, trace)) == pytest.approx(5.0)
    assert read(make_run("qwen1.5-4b-serve", {},
                         Trace([], [], (0.0, 1e9)))) is None
