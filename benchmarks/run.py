"""Benchmark entry: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  The §Roofline harness
(benchmarks/roofline.py) and the multi-pod dry-run (repro.launch.dryrun) are
separate long-running entries — this file covers the paper-table benchmarks.

The comm, hier, faults, cohort and serve rows are additionally written to
``BENCH_comm.json`` / ``BENCH_hier.json`` / ``BENCH_faults.json`` /
``BENCH_cohort.json`` / ``BENCH_serve.json``
(machine-readable: name, wall-us, bytes) so the codec/transport/
aggregation-tree/robustness perf trajectory is tracked across PRs instead of
living only in stdout.
"""
from __future__ import annotations

import json
import os
import re
import sys
import traceback

_BYTES_RE = re.compile(r"(?:^|;)bytes=(\d+)")


def write_comm_json(rows, path: str = "BENCH_comm.json") -> None:
    """Persist comm benchmark rows: [{name, us, bytes|null, derived}]."""
    out = []
    for name, us, derived in rows:
        m = _BYTES_RE.search(derived)
        out.append({"name": name, "us": round(float(us), 1),
                    "bytes": int(m.group(1)) if m else None,
                    "derived": derived})
    with open(path, "w") as f:
        json.dump({"rows": out}, f, indent=1)
        f.write("\n")


def main() -> int:
    """Run every module, past failures; return how many modules failed."""
    from benchmarks import bench_cohort, bench_comm, bench_efbv
    from benchmarks import bench_faults, bench_fedp3, bench_hier
    from benchmarks import bench_kernels, bench_scafflix, bench_scafflix_nn
    from benchmarks import bench_serve, bench_sppm, bench_symwanda
    from benchmarks.common import emit, module_trace, now_s, trace_dir
    from repro.obs import trace as obs_trace
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()

    modules = [
        ("comm(codecs/ledger/topology)", bench_comm),
        ("hier(aggregation-trees,Ch.5)", bench_hier),
        ("faults(robustness)", bench_faults),
        ("cohort(million-client)", bench_cohort),
        ("serve(personalized-deltas)", bench_serve),
        ("efbv(Fig2.2)", bench_efbv),
        ("scafflix(Fig3.1/3.3)", bench_scafflix),
        ("scafflix_nn(Fig3.2)", bench_scafflix_nn),
        ("fedp3(Fig4.2/4.4/Tab4.2)", bench_fedp3),
        ("sppm(Fig5.1-5.6)", bench_sppm),
        ("symwanda(Tab6.3-6.6)", bench_symwanda),
        ("kernels", bench_kernels),
    ]
    json_sinks = {
        id(bench_comm): ("BENCH_COMM_JSON", "BENCH_comm.json"),
        id(bench_hier): ("BENCH_HIER_JSON", "BENCH_hier.json"),
        id(bench_faults): ("BENCH_FAULTS_JSON", "BENCH_faults.json"),
        id(bench_cohort): ("BENCH_COHORT_JSON", "BENCH_cohort.json"),
        id(bench_serve): ("BENCH_SERVE_JSON", "BENCH_serve.json"),
    }
    print("name,us_per_call,derived")
    failed = 0
    for label, mod in modules:
        t0 = now_s()
        short = mod.__name__.rsplit(".", 1)[-1].removeprefix("bench_")
        try:
            # with REPRO_TRACE=1 each module's spans land in its own
            # TRACE_<module>.jsonl next to the CSV rows
            with module_trace(short, module=mod.__name__):
                rows = mod.run()
            emit(rows)
            if obs_trace.enabled():
                print(f"# {label} trace -> "
                      f"{os.path.join(trace_dir(), f'TRACE_{short}.jsonl')}",
                      file=sys.stderr)
            if id(mod) in json_sinks:
                env, default = json_sinks[id(mod)]
                path = os.environ.get(env, default)
                write_comm_json(rows, path)
                print(f"# {label} rows -> {path}", file=sys.stderr)
        except Exception as e:  # noqa: BLE001 — keep the suite going
            failed += 1
            traceback.print_exc(file=sys.stderr)
            print(f"{label}/ERROR,0,{type(e).__name__}:{e}", file=sys.stderr)
        print(f"# {label} done in {now_s()-t0:.1f}s", file=sys.stderr)
    return failed


if __name__ == "__main__":
    sys.exit(1 if main() else 0)
