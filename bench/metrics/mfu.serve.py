"""Model step: model FLOPs of the tokens the engine processed in the
window (2 per matmul weight per token, plus causal attention), over the
window times the chip's bf16 peak, in percent."""
from bench import counts


def read(run):
    t0, t1 = run.window
    flops = sum(counts.decode_step_flops(run.extra["model"], st["grants"])
                for st in run.steps if t0 <= st["t0"] < t1)
    return 100.0 * flops / (run.window_s * run.peaks["bf16_flops"]) if flops else None
