"""Model FLOP utilisation of training, in percent: the operations the
forward and backward passes need for the window's tokens (causal attention
counted, recomputation not), over the window's length, the chips and the
chip's bf16 peak."""
from benchmarks.chip import flops


def read(run):
    f = run.facts
    if not f.get("tokens"):
        return None
    work = flops.train_flops_per_token(run.config, f["seq_len"]) * f["tokens"]
    return 100.0 * work / (f["window_s"] * run.chips
                           * run.peaks["bf16_flops_per_s"])
