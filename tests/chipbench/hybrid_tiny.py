"""A tiny hybrid cell beside the tiny dense ones of ``conftest``: the
published granite-4.0-h-micro configuration cut to one period of 10
layers (attention at position 5) and small widths, served in closed waves
of 2 requests (16-token prompts, 40 tokens out) through the hybrid
generator, with the committed limit of the chip
cell (its gap is in units of each row's spread of logits)."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from conftest import REPO, make_root, write_json

CHIP_CELL = "granite-h-micro-serve-batch"
HYBRID = "tiny-hybrid-serve"


def published() -> dict:
    return json.loads((REPO / "benchmarks" / "chip" / "configs"
                       / "granite-4.0-h-micro-serve.json").read_text())


def tiny_hybrid() -> dict:
    c = published()
    return dict(c, name="tiny-hybrid", hidden_size=64,
                shared_intermediate_size=128, intermediate_size=128,
                num_attention_heads=4, num_key_value_heads=2,
                num_hidden_layers=10, layer_types=c["layer_types"][:10],
                mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
                mamba_chunk_size=8, vocab_size=512)


def make_hybrid_root(dst: Path) -> Path:
    root = make_root(dst)
    chip = root / "benchmarks" / "chip"
    write_json(chip / "configs" / "tiny-hybrid.json", tiny_hybrid())
    mix = json.loads((chip / "workloads" / "batch-32x1024-out512.json").read_text())
    # 40 decode steps: a stale SSM state shows only as decoded tokens'
    # contributions go missing from it
    mix.update(slots=2, max_len=64, prompt_len=16, max_new=40,
               corpus_tokens=4096, wave_s_min=0.001)
    write_json(chip / "workloads" / f"{HYBRID}.json", mix)
    shutil.copy(chip / "limits" / f"{CHIP_CELL}.json",
                chip / "limits" / f"{HYBRID}.json")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-hybrid", "source": "test",
                            "file": "benchmarks/chip/configs/tiny-hybrid.json",
                            "reduced": [], "why": "CPU test size"})
    spec["workloads"].append({"name": HYBRID, "config": "tiny-hybrid",
                              "traffic": HYBRID, "chips": 1,
                              "why": "CPU test size"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if CHIP_CELL in m.get("workloads", []):
            m["workloads"].append(HYBRID)
    write_json(root / "BENCHMARK.json", spec)
    return root
