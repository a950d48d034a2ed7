"""Collectives: per step, the all-reduce device time during which no
other operation runs on that device (mean over the chips), in the traced
steps."""


def read(run):
    if run.trace is None or not run.extra.get("trace_steps"):
        return None
    return 1e3 * run.trace.exposed_collective_s / run.extra["trace_steps"]
