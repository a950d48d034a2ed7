"""Engine host loop: the mean, over the engine steps in the traced
window, of a step's host work, its ``engine:step`` span less its waits
on the device (the ``engine:sync`` and ``engine:sync_overflow`` spans),
in ms."""
from bench import engine_trace


def read(run):
    et = engine_trace.of(run)
    if et is None or not et.steps:
        return None
    return 1e3 * sum(st.host_s for st in et.steps) / len(et.steps)
