"""95th percentile, over every request that received two or more output
tokens, of (last token - first token) / (tokens received - 1), on the
client's clock; a request still streaming when the run stopped waiting
counts by the tokens it had received."""
from bench.common import percentile


def read(run):
    vals = [(r["last"] - r["first"]) * 1e3 / (r["n_recv"] - 1)
            for r in run.requests if r["first"] is not None and r["n_recv"] > 1]
    return percentile(vals, 95) if vals else None
