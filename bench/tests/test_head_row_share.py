"""The reader of the LM head's row share over hand-made engine steps."""
from bench import common, engine_trace as E

READ = common.metric_reader("head_row_share.chat")


def _run(steps):
    run = common.Run(workload="w", kind="serve_open", chips=1, cfg=None, traffic={},
                     peaks={})
    run.trace = object()
    run.extra["engine_trace"] = E.EngineTrace(busy_s=1.0, module_s={}, steps=steps,
                                              idle_gaps=[], decode_device=[])
    return run


def _step(kind, **args):
    return E.Step(0.0, 1.0, {"step": 0, "kind": kind, "tokens": 1, **args}, {})


def test_share_over_mixed_steps():
    steps = [_step("mixed", rows=513, head_rows=32), _step("mixed", rows=513, head_rows=32),
             _step("mixed", rows=100, head_rows=100), _step("decode", rows=32, head_rows=32)]
    assert abs(READ(_run(steps)) - 100.0 * 164 / 1126) < 1e-9


def test_nothing_to_read():
    # spans without the arguments, as an engine without the counter makes
    assert READ(_run([_step("mixed"), _step("decode")])) is None
    # no mixed step in the window
    assert READ(_run([_step("decode", rows=32, head_rows=32)])) is None
    untraced = _run([])
    untraced.trace = None
    del untraced.extra["engine_trace"]
    assert READ(untraced) is None
