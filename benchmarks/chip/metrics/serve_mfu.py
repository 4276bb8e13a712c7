"""Model FLOP utilisation of serving, in percent: the operations the
window's prefills and decode steps need, over the window's length and the
chip's bf16 peak."""
from benchmarks.chip import flops


def read(run):
    f = run.facts
    if not f.get("decode_steps"):
        return None
    c = run.config
    work = flops.prefill_flops(c, f["prefill_rows"], f["prompt_len"])
    work += sum(flops.decode_flops(c, pos) for pos in f["decode_positions"])
    return 100.0 * work / (f["window_s"] * run.chips
                           * run.peaks["bf16_flops_per_s"])
