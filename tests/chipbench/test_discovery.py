"""A new configuration, traffic mix, cell and per-layer metric are picked
up from new files and new entries of the spec alone: no file of the
benchmark is edited."""
from __future__ import annotations

import hashlib
import json
import time

import jax
import pytest

from benchmarks.chip import harness

from conftest import REPO, SERVE, TINY, TRAIN, make_root, write_json

READER = '''"""Steps in the window (a count the generator hands over)."""


def read(run):
    return run.facts.get("steps")
'''


def digest(root):
    chip = root / "benchmarks" / "chip"
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(chip.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """The tiny benchmark, then a later change that only adds."""
    root = make_root(tmp_path_factory.mktemp("grown"))
    before = digest(root)
    chip = root / "benchmarks" / "chip"
    write_json(chip / "configs" / "tiny-wide.json",
               dict(TINY, name="tiny-wide", num_attention_heads=8,
                    num_key_value_heads=8, num_hidden_layers=1))
    mix = json.loads((chip / "workloads" / f"{TRAIN}.json").read_text())
    write_json(chip / "workloads" / "tiny-train-long.json",
               dict(mix, seq_len=48, global_batch=4))
    write_json(chip / "limits" / "tiny-wide-train.json",
               json.loads((chip / "limits" / f"{TRAIN}.json").read_text()))
    (chip / "metrics" / "window_steps.py").write_text(READER)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-wide", "source": "test",
                            "file": "benchmarks/chip/configs/tiny-wide.json",
                            "reduced": [], "why": "added by a later change"})
    spec["workloads"].append({"name": "tiny-wide-train", "config": "tiny-wide",
                              "traffic": "tiny-train-long", "chips": 1,
                              "why": "added by a later change"})
    for m in spec["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("tiny-wide-train")
    spec["per_layer"].append({"name": "window_steps", "unit": "steps",
                              "better": "higher", "source": "program_counter",
                              "layer": "host loop (training/loop.py)",
                              "moves": "train_tokens_per_s"})
    write_json(root / "BENCHMARK.json", spec)
    after = digest(root)
    assert all(after[p] == h for p, h in before.items())
    return root


def test_new_cell_found(grown):
    cell = harness.load_cell(grown, "tiny-wide-train")
    assert cell.config["num_attention_heads"] == 8
    assert cell.traffic["seq_len"] == 48
    assert [m["name"] for m in cell.end_to_end()] == ["train_tokens_per_s",
                                                     "setup_s"]
    # a metric without `workloads` goes to every cell reporting what it moves
    assert "window_steps" in [m["name"] for m in cell.per_layer()]
    assert "window_steps" not in [m["name"] for m in
                                  harness.load_cell(grown, SERVE).per_layer()]


def test_new_cell_runs_with_new_metric(grown):
    result = harness.run_cell(grown, "tiny-wide-train", 2**31 + 3, 0.3, True,
                              time.perf_counter(), devices=jax.devices()[:1])
    assert result["correct"], result["checks"]
    assert result["metrics"]["window_steps"]["value"] == result["attempted"]
    assert result["metrics"]["window_steps"]["unit"] == "steps"
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes", "busy_s", "window_s"}
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_unknown_names_are_errors(grown):
    with pytest.raises(harness.BenchmarkError):
        harness.load_cell(grown, "no-such-cell")
    with pytest.raises(harness.BenchmarkError):
        harness.peaks("TPU v99", REPO)
