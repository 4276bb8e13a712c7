"""Operation and byte counts against arithmetic by hand from the published
shapes of both configurations."""
from __future__ import annotations

import json

import pytest

from benchmarks.chip import flops

from conftest import REPO


def config(name):
    return json.loads((REPO / "benchmarks" / "chip" / "configs"
                       / f"{name}.json").read_text())


@pytest.fixture(scope="module")
def danube():
    return config("danube-1.8b-train")


@pytest.fixture(scope="module")
def qwen():
    return config("qwen1.5-4b-serve")


def test_danube_train(danube):
    # q and o: 2560 x (32 x 80); k and v: 2560 x (8 x 80); gated MLP 3 x 2560 x 6912
    layer = 2 * 2560 * 2560 + 2 * 2560 * 640 + 3 * 2560 * 6912
    assert flops.layer_matmul_params(danube) == layer == 69_468_160
    assert flops.matmul_params(danube) == 7 * layer + 2560 * 32000 == 568_197_120
    # causal attention at 4096 within the 4096 window: mean context 4097/2,
    # QK^T and PV 4 x 32 x 80 operations a position, 7 layers
    attn = 4 * 32 * 80 * (4097 / 2) * 7
    assert attn == 146_836_480
    assert flops.attention_flops(danube, range(4096)) / 4096 == attn
    assert flops.train_flops_per_token(danube, 4096) == \
        3 * (2 * 568_197_120 + attn) == 3_849_692_160


def test_window_caps_context(danube):
    assert flops.context(danube, 100) == 101
    assert flops.context(danube, 5000) == 4096


def test_qwen_decode(qwen):
    layer = 4 * 2560 * 2560 + 3 * 2560 * 6912            # 20 heads of 128
    assert flops.layer_matmul_params(qwen) == layer == 79_298_560
    assert flops.layer_params(qwen) == layer + 2 * 2560 + 3 * 2560
    matmul = 40 * layer + 2560 * 151936
    assert flops.matmul_params(qwen) == matmul == 3_560_898_560
    # 8 sequences, each at position 639 (640 positions live), full causal:
    # the qwen config turns its published sliding window off
    positions = [639] * 8
    assert flops.context(qwen, 40_000) == 40_001
    attn = 4 * 20 * 128 * 640 * 8 * 40
    assert flops.decode_flops(qwen, positions) == 2 * matmul * 8 + attn
    weights = (40 * (layer + 2 * 2560 + 3 * 2560) + 2560 * 151936 + 2560) * 2
    kv_per_position = 40 * 2 * 20 * 128 * 2
    assert kv_per_position == 409_600
    assert flops.decode_bytes(qwen, positions) == \
        weights + 8 * 2560 * 2 + 8 * 640 * kv_per_position == 9_220_019_200


def test_qwen_prefill(qwen):
    blocks = 40 * 79_298_560
    per_row = (2 * blocks * 512 + 2 * 2560 * 151936
               + 4 * 20 * 128 * (512 * 513 // 2) * 40)
    assert flops.prefill_flops(qwen, 8, 512) == 8 * per_row


def test_weight_bytes(qwen):
    assert flops.weight_bytes(qwen) == pytest.approx(7.90e9, rel=1e-3)
