"""Model step: device time of the sampler's jitted programs over the
device's busy time in the traced window, in percent."""
from bench import engine_trace

SAMPLER = ("_sampled_tokens", "_greedy_tokens")  # jitted names in serve/sampling.py


def read(run):
    et = engine_trace.of(run)
    if et is None or et.busy_s <= 0:
        return None
    s = et.modules_s(SAMPLER)
    return None if s is None else 100.0 * s / et.busy_s
