"""Run one cell of the chip benchmark and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the TPU chips the
cell asks for; nothing falls back to another device.  Set-up (imports,
weights made on the device from the seed, compile-cache load, warm-up) is
timed from process start to the window's start.  The window then lasts
``--seconds`` (a traced run, ``--trace 1``, at most ten seconds), and after
it the check compares what the timed path produced with the plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit.
The same numbers are the last lines of standard error.  A run that finds no
TPU, fewer chips than the cell asks for, or not the program beside it exits
with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # repro: noqa[RL003] set-up starts here

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def _finite(x):
    if isinstance(x, float) and not math.isfinite(x):
        return None
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_finite(v) for v in x]
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from benchmarks.chip import harness

    try:
        from repro.utils.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"error: the program is not beside the benchmark: {e}",
              file=sys.stderr)
        return 2
    import jax

    use_compile_cache()
    # every program the cell runs is kept, however small, so that a second
    # run in the same checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        result = harness.run_cell(ROOT, args.workload, args.seed,
                                  args.seconds, bool(args.trace), T_START)
    except harness.BenchmarkError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    for line in harness.check_lines(result):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(_finite(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
