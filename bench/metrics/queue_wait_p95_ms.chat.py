"""Front-end and admission: 95th percentile of due time to slot
admission (``Request.admitted_at``); a request never admitted counts at
the time it waited until the run stopped waiting (a lower bound)."""
from bench.common import percentile


def read(run):
    end = run.extra["wait_end"]
    vals = [((r["admitted"] if r["admitted"] is not None else end) - r["due_at"]) * 1e3
            for r in run.requests]
    return percentile(vals, 95) if vals else None
