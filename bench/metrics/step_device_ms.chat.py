"""Model step: device busy time per engine step in the traced window."""


def read(run):
    if run.trace is None:
        return None
    t0, t1 = run.extra["trace_window"]
    n = len([1 for name, a, _ in run.spans.records if name == "model_step" and t0 <= a < t1])
    return 1e3 * run.trace.busy_s / n if n else None
