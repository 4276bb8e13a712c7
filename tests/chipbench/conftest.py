"""A small copy of the chip benchmark that the CPU can run.

``bench_root`` copies ``benchmarks/chip`` and ``BENCHMARK.json`` into a
temporary directory and adds, as files of their own, a tiny configuration
of the same dense decoder family, one training and one serving mix at tiny
sizes, their cells, and a CPU entry in the peaks table.  The harness then
runs every step of a cell except the search for a TPU.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "src"))

TINY = {
    "name": "tiny-dense", "source": "test", "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 256, "sliding_window": 24, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-5, "hidden_act": "silu", "qkv_bias": True,
    "tie_word_embeddings": False, "dtype": "bfloat16",
}
TRAIN = "tiny-train"
SERVE = "tiny-serve"
# The tiny training cell's own limits, set like the chip cells' from CPU
# readings at this size: sound runs read loss 1e-4..2.2e-4, grad 1.2e-3..
# 6.8e-3, change 1.9e-2..5.3e-2 (bf16 rounding moves a larger share of a
# small leaf); the control reads 5.8e-3, 2.6e-2 and 0.12.  Serving keeps the
# chip cell's limit: its gap is in units of each row's spread of logits.
TINY_TRAIN_LIMITS = {"loss_gap": {"limit": 1.5e-3}, "grad_gap": {"limit": 1.5e-2},
                     "change_gap": {"limit": 0.3}}


def write_json(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


def make_root(dst: Path) -> Path:
    """The benchmark as committed, plus the tiny cells."""
    chip = dst / "benchmarks" / "chip"
    shutil.copytree(REPO / "benchmarks" / "chip", chip,
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    write_json(chip / "configs" / "tiny-dense.json", TINY)
    train = json.loads((chip / "workloads" / "train-dense-4x4096.json").read_text())
    train.update(global_batch=2, seq_len=32, corpus_tokens=4096)
    write_json(chip / "workloads" / "tiny-train.json", train)
    serve = json.loads((chip / "workloads" / "batch-8x512-out256.json").read_text())
    serve.update(slots=2, max_len=24, prompt_len=16, max_new=8,
                 corpus_tokens=4096, wave_s_min=0.001)
    write_json(chip / "workloads" / "tiny-serve.json", serve)
    write_json(chip / "limits" / f"{TRAIN}.json", TINY_TRAIN_LIMITS)
    shutil.copy(chip / "limits" / "qwen4b-serve-batch.json",
                chip / "limits" / f"{SERVE}.json")
    peaks = json.loads((chip / "peaks.json").read_text())
    peaks["devices"]["cpu"] = {"bf16_flops_per_s": 1e12,
                               "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
    write_json(chip / "peaks.json", peaks)
    spec["configs"].append({"name": "tiny-dense", "source": "test",
                            "file": "benchmarks/chip/configs/tiny-dense.json",
                            "reduced": [], "why": "CPU test size"})
    spec["workloads"] += [
        {"name": TRAIN, "config": "tiny-dense", "traffic": TRAIN, "chips": 1,
         "why": "CPU test size"},
        {"name": SERVE, "config": "tiny-dense", "traffic": SERVE, "chips": 1,
         "why": "CPU test size"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            kind = "train" if "train" in m.get("moves", m["name"]) else "serve"
            m["workloads"].append(TRAIN if kind == "train" else SERVE)
    write_json(dst / "BENCHMARK.json", spec)
    return dst


@pytest.fixture(scope="module")
def bench_root(tmp_path_factory) -> Path:
    return make_root(tmp_path_factory.mktemp("bench"))
