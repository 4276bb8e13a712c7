"""Hybrid decode step's share of its roofline, in percent: the HBM bytes one
decode step needs (every weight once, the embedding rows, each Mamba
layer's SSM state and conv tail of every live slot read and written, the
attention layers' live K/V) over the chip's bandwidth, or its operations
over the bf16 peak where those take longer, divided by the mean device time
of the decode program (``jit_decode``) in the trace.  At 32 slots the
bytes bound it: the operations take about a tenth of their time.

Silent where the window ran no decode program or the run is not of a
hybrid configuration."""
from benchmarks.chip import devtrace, hybrid_flops

PROGRAM = "jit_decode"


def read(run):
    f = run.facts
    if "ssm_state_bytes" not in f or not f.get("decode_positions"):
        return None
    runs = [d for name, ds in devtrace.module_runs(run.trace, PROGRAM).items()
            for d in ds]
    if not runs:
        return None
    device_s = sum(runs) / len(runs) / 1e9
    c, peak = run.config, run.peaks
    steps = f["decode_positions"]
    need_bytes = sum(hybrid_flops.decode_bytes(c, p) for p in steps) / len(steps)
    need_ops = sum(hybrid_flops.decode_flops(c, p) for p in steps) / len(steps)
    bound_s = max(need_bytes / peak["hbm_bytes_per_s"],
                  need_ops / peak["bf16_flops_per_s"])
    return 100.0 * bound_s / device_s
