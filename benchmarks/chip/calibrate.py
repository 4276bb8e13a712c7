"""Readings that a cell's limits are set from, on the chip at its own size.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seconds <s> \\
        --seeds <n>... [--controls <k>] [--fault <name>]

For every seed, one run of the cell as ``run.py`` makes it (a short window
will do): the numbers its check compares are the program's readings, from
which a limit's lower reading comes.  For the first ``--controls`` seeds,
the generator's ``control_readings`` on the same inputs: the control (the
reference computed in float8 in the program's place) and the faults planted
in the reference; their readings bound a limit from above.  ``--fault``
plants a fault in the program instead (see ``FAULTS``) and reports the
program's readings under it.  All in one process: set-up is paid once per
run, compilation once.  Prints one JSON line per run.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _stale_cache():
    from repro.training.serving import ContinuousBatcher

    orig = ContinuousBatcher._model_decode

    def decode(self, tok):
        logits, _ = orig(self, tok)
        return logits, self.cache

    ContinuousBatcher._model_decode = decode


FAULTS = {"cache_unchanged": _stale_cache}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=0)
    ap.add_argument("--fault", choices=sorted(FAULTS))
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from benchmarks.chip import harness
    from benchmarks.chip.harness import now
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if args.fault:
        FAULTS[args.fault]()
    cell = harness.load_cell(ROOT, args.workload)
    devices = harness.tpu_devices(cell.chips)
    gen = harness.load_generator(ROOT, cell.traffic["kind"])
    for i, seed in enumerate(args.seeds):
        t0 = now()
        ctx = harness.Context(cell, seed, args.seconds,
                              harness.Window(False), t0)
        out = gen.run(ctx, devices)
        line = {"seed": seed, "fault": args.fault, "correct": out.correct,
                "checks": {c.name: c.value for c in out.checks},
                "end_to_end": out.end_to_end, "setup_s": ctx.setup_s,
                "memory_peak_bytes": out.memory_peak_bytes,
                "run_s": now() - t0}
        if i < args.controls:
            t1 = now()
            inputs = out.facts.get("check_rows",
                                   out.facts.get("check_sequences"))
            line["controls"] = gen.control_readings(cell, seed, inputs)
            line["controls_s"] = now() - t1
        harness.free_device_memory()
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
