"""SPMD train step: forward and backward FLOPs of the kept tokens (6 per
matmul weight per token plus bidirectional attention, no recomputation)
over the window times the chips times the bf16 peak, in percent."""
from bench import counts


def read(run):
    seq = run.traffic["seq_len"]
    tokens = sum(s["kept_rows"] for s in run.steps) * seq
    flops = counts.train_step_flops(run.extra["model"], tokens, seq)
    return 100.0 * flops / (run.window_s * run.chips * run.peaks["bf16_flops"])
