"""The command line refuses to run where it cannot measure: off a TPU, and
in a checkout that holds the benchmark without the program."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

from conftest import REPO

ARGS = ["--workload", "danube-train-dense", "--seed", str(2**31 + 5),
        "--seconds", "1", "--trace", "0"]


def run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_no_result():
    out = run(REPO)
    assert out.returncode == 2, out.stderr[-2000:]
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_benchmark_alone_no_result(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for rel in ("benchmarks/chip", "tests/chipbench"):
        shutil.copytree(REPO / rel, tmp_path / rel,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = run(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
