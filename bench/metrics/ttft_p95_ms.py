"""95th percentile, over every request due in the window, of the time
from its due time to the first token its client received.  A request
that got no first token counts at the time it waited until the run
stopped waiting (a lower bound), so the tail stays a number."""
from bench.common import percentile


def read(run):
    end = run.extra["wait_end"]
    vals = [((r["first"] if r["first"] is not None else end) - r["due_at"]) * 1e3
            for r in run.requests]
    return percentile(vals, 95) if vals else None
