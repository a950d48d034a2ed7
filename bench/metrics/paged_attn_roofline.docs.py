"""Pallas paged attention: the least time the chip needs for the
kernel's logical work, over the kernel's device time in the traced
window, in percent.  The logical work of a call is that of one layer of
one engine step: every query over its causal context, each active slot's
live keys and values read once, queries and outputs
(``bench.counts.paged_attention_work``); the pool's size and any
relayout never enter."""
from bench import counts
from bench.common import log

KERNEL = "paged_flash_attention"  # the kernel's HLO name in the device trace


def read(run):
    if run.trace is None:
        return None
    kernel_s = run.trace.kernel_s(KERNEL)
    if not kernel_s:
        top = sorted(run.trace.op_s, key=run.trace.op_s.get, reverse=True)[:20]
        log(f"paged_attn_roofline: no device operation named {KERNEL!r} in the "
            f"trace; its longest operations are {top}")
        return None
    t0, t1 = run.extra["trace_window"]
    model = run.extra["model"]
    least = 0.0
    for st in run.steps:
        if t0 <= st["t0"] and st["t1"] <= t1:
            f, b = counts.paged_attention_work(model, st["grants"])
            least += model["n_layers"] * counts.roofline_s(f, b, run.peaks)[0]
    return 100.0 * least / kernel_s if least else None
