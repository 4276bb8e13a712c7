"""Hybrid prefill's share of the chip's bf16 peak, in percent: the
operations a prefill of (slots, prompt) needs (the matmuls, causal NoPE
attention, and the chunked SSD's intra-chunk and state terms at the
published chunk; ``hybrid_flops.prefill_flops``) over the mean device time
of the prefill program (``jit_prefill``) in the trace.

Silent where the window ran no prefill program or the run is not of a
hybrid configuration."""
from benchmarks.chip import devtrace, hybrid_flops

PROGRAM = "jit_prefill"


def read(run):
    f = run.facts
    if "ssm_state_bytes" not in f or not f.get("slots"):
        return None
    runs = [d for name, ds in devtrace.module_runs(run.trace, PROGRAM).items()
            for d in ds]
    if not runs:
        return None
    device_s = sum(runs) / len(runs) / 1e9
    work = hybrid_flops.prefill_flops(run.config, f["slots"], f["prompt_len"])
    return 100.0 * work / (device_s * run.peaks["bf16_flops_per_s"])
