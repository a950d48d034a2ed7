"""Model step: the median, over the decode-only engine steps that began
in the window (every grant one token, so the engine ran its
decode-capacity program), of the benchmark's span around the step's
layout, dispatch and syncs (``bench:model_step``, around
``ContinuousBatcher._run_packed``), in ms.  The whole window, not the
traced one: the chat schedule puts only mixed steps in the traced
middle."""
import statistics


def read(run):
    w0, w1 = run.window
    decode = [st["t1"] - st["t0"] for st in run.steps
              if w0 <= st["t0"] < w1 and all(n == 1 for _, _, n in st["grants"])]
    return 1e3 * statistics.median(decode) if decode else None
