"""Front-end and admission: the share of the engine steps in the traced
window whose admission stopped, with a request still queued, because
the page pool could not reserve the head request's worst case (the
``admit`` argument of ``engine:step`` is "pool"), in percent."""
from bench import engine_trace


def read(run):
    et = engine_trace.of(run)
    if et is None or not et.steps:
        return None
    pool = sum(st.args.get("admit") == "pool" for st in et.steps)
    return 100.0 * pool / len(et.steps)
