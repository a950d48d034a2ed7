"""Model step: the share of the mixed steps' program rows that went
through the LM head and the sampler, over the traced window's
``engine:step`` spans whose ``kind`` is "mixed": 100 x the sum of their
``head_rows`` over the sum of their ``rows``, in percent.  None where the
spans carry no such arguments."""
from bench import engine_trace


def read(run):
    et = engine_trace.of(run)
    if et is None:
        return None
    mixed = [st.args for st in et.steps
             if st.kind == "mixed" and "rows" in st.args and "head_rows" in st.args]
    rows = sum(a["rows"] for a in mixed)
    return 100.0 * sum(a["head_rows"] for a in mixed) / rows if rows else None
