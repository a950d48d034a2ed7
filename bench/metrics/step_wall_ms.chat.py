"""Engine host loop: the window over the engine steps that began in it
(a benchmark span around ``ContinuousBatcher.step``)."""


def read(run):
    t0, t1 = run.window
    n = len([1 for name, a, _ in run.spans.records if name == "step" and t0 <= a < t1])
    return 1e3 * run.window_s / n if n else None
