"""Decode step's share of its roofline, in percent: the HBM bytes one
decode step needs (every weight once, the live cache positions of each
sequence) over the chip's bandwidth, divided by the decode program's device
time per step in the trace.  At this batch the step is bound by bandwidth:
its operations take under a tenth of the bytes' time.

The decode program is the serving program that ran most often in the
window: it runs once every step, the prefill once a wave."""
from benchmarks.chip import devtrace, flops

SERVING_PROGRAMS = ("jit__lambda", "jit_decode")


def read(run):
    f = run.facts
    runs = {name: ts for name, ts in devtrace.module_runs(run.trace, "").items()
            if name.startswith(SERVING_PROGRAMS)}
    if not runs or not f.get("decode_positions"):
        return None
    durations = max(runs.values(), key=len)
    device_s = sum(durations) / len(durations) / 1e9
    c, peak = run.config, run.peaks
    steps = f["decode_positions"]
    need_bytes = sum(flops.decode_bytes(c, pos) for pos in steps) / len(steps)
    need_ops = sum(flops.decode_flops(c, pos) for pos in steps) / len(steps)
    bound_s = max(need_bytes / peak["hbm_bytes_per_s"],
                  need_ops / peak["bf16_flops_per_s"])
    return 100.0 * bound_s / device_s
