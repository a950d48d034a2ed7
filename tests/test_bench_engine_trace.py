"""The benchmark's reading of the engine's spans: a trace recorded here
yields the ``engine:`` spans with their arguments, each jitted program's
device seconds and idle gaps labelled by the innermost program span; the
five readers of them (``bench/metrics``) on hand-made runs.
"""
import glob
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import common, engine_trace as E  # noqa: E402

TA = jax.profiler.TraceAnnotation


def _record(tmp_path, body):
    d = str(tmp_path / "trace")
    jax.profiler.start_trace(d)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return E.load(E.trace_file(d))


def test_recorded_spans_modules_and_gaps(tmp_path):
    @jax.jit
    def f(x):
        return jnp.tanh(x @ x).sum()

    @jax.jit
    def g(x):
        return (2 * x).sum()

    x = jnp.ones((256, 256))
    f(x).block_until_ready(), g(x).block_until_ready()

    def body():
        for i, kind in enumerate(("mixed", "decode")):
            with TA("engine:step", step=i) as ann:
                with TA("engine:dispatch"):
                    y, z = f(x), g(x)
                with TA("engine:sync"):
                    np.asarray(y), np.asarray(z)
                with TA("engine:emit"):
                    time.sleep(0.03)  # the device idles while the host works
                ann.set_metadata(kind=kind, tokens=3 + i, admit="pool" if i else "")
            with TA("frontend:publish"):
                pass

    spans, ops = _record(tmp_path, body)
    assert {s.name for s in spans} == {"engine:step", "engine:dispatch", "engine:sync",
                                       "engine:emit", "frontend:publish"}
    et = E.reduce(spans, ops)
    assert [st.args for st in et.steps] == [
        {"step": 0, "kind": "mixed", "tokens": 3},
        {"step": 1, "kind": "decode", "tokens": 4, "admit": "pool"}]
    for st in et.steps:
        assert set(st.phases) == {"dispatch", "sync", "emit"}
        assert st.phases["emit"] >= 0.03
        assert st.host_s == pytest.approx(st.wall_s - st.phases["sync"])
        assert st.device_window is not None
    assert et.module_s["jit_f"] > 0 and et.module_s["jit_g"] > 0
    assert et.modules_s(("jit_f", "jit_g")) == et.module_s["jit_f"] + et.module_s["jit_g"]
    assert sum(et.module_s.values()) <= et.busy_s * 1.0001 + 1e-9
    # the longest idle gap is the sleep, inside both engine:step and
    # engine:emit: the innermost names it
    label, gap = et.idle_gaps[0]
    assert label == "engine:emit" and gap >= 0.02
    [(window, busy)] = et.decode_device  # the one decode step
    assert 0 < busy <= window
    assert E.summary(et)["decode_steps"] == 1


def test_operations_take_the_module_that_holds_them():
    """A TPU plane names programs on its ``XLA Modules`` line; an
    operation belongs to the one whose interval holds its midpoint."""
    modules = [(2.0, 3.0, "jit_g"), (0.0, 1.0, "jit_f")]
    ops = [(0.1, 0.2), (0.9, 1.05), (1.5, 1.6), (2.5, 3.5)]
    assert E._in_modules(ops, modules) == ["jit_f", "jit_f", "?", "jit_g"]
    assert E._module_name("jit__sampled_tokens(42)") == "jit__sampled_tokens"


def test_recorded_engine_step(tmp_path):
    """The program's own spans: a paged, packed engine step under the
    profiler."""
    from repro.models import ModelConfig
    from repro.models.model import init_params
    from repro.serve import ContinuousBatcher, Request

    cfg = ModelConfig(name="bench-spans-t", n_layers=2, d_model=32, n_heads=2,
                      n_kv_heads=1, d_ff=64, vocab_size=101, layer_pattern="LG",
                      sliding_window=6, dtype="float32", remat=False)
    eng = ContinuousBatcher(init_params(jax.random.PRNGKey(0), cfg), cfg,
                            batch_slots=2, max_len=24, chunk_size=4, packed=True,
                            cache="paged", page_size=4)
    for i, n in enumerate((5, 9, 3)):
        eng.submit(Request(uid=i, prompt=list(range(1, n + 1)), max_new_tokens=3))
    eng.run()  # compile outside the trace
    for i, n in enumerate((5, 9, 3)):
        eng.submit(Request(uid=10 + i, prompt=list(range(2, n + 2)), max_new_tokens=3))
    n0 = eng.steps
    spans, ops = _record(tmp_path, eng.run)
    et = E.reduce(spans, ops)
    assert [st.args["step"] for st in et.steps] == list(range(n0, eng.steps))
    for st, rec in zip(et.steps, eng.step_stats[n0:]):
        assert st.kind == rec.kind
        assert st.args["tokens"] == rec.scheduled_tokens
        assert st.args.get("admit") == rec.admit_blocked
        assert set(st.phases) == set(rec.phases)
    assert "slots" in [st.args.get("admit") for st in et.steps]
    assert {"decode", "mixed"} == {st.kind for st in et.steps}
    assert any("_packed_engine_step" in m for m in et.module_s)
    assert any("_greedy_tokens" in m for m in et.module_s)


def test_recorded_engine_step_head_rows(tmp_path):
    """The ``engine:step`` span carries the program's ``rows`` and the
    ``head_rows`` of them that reached the LM head, as ``StepStats`` has
    them: a mixed step sends one row a slot, a decode step all of them."""
    from repro.models import ModelConfig
    from repro.models.model import init_params
    from repro.serve import ContinuousBatcher, Request

    cfg = ModelConfig(name="bench-spans-t", n_layers=2, d_model=32, n_heads=2,
                      n_kv_heads=1, d_ff=64, vocab_size=101, layer_pattern="LG",
                      sliding_window=6, dtype="float32", remat=False)
    eng = ContinuousBatcher(init_params(jax.random.PRNGKey(0), cfg), cfg,
                            batch_slots=2, max_len=24, chunk_size=4, token_budget=8,
                            packed=True, cache="paged", page_size=4)

    def submit(uid0):
        for i, n in enumerate((7, 2, 5)):
            eng.submit(Request(uid=uid0 + i, prompt=list(range(1, n + 1)),
                               max_new_tokens=3))

    submit(0)
    eng.run()  # compile outside the trace
    n0 = eng.steps
    submit(10)
    spans, ops = _record(tmp_path, eng.run)
    et = E.reduce(spans, ops)
    got = [(st.kind, st.args["rows"], st.args["head_rows"]) for st in et.steps]
    assert got == [(rec.kind, rec.rows, rec.head_rows) for rec in eng.step_stats[n0:]]
    assert set(got) == {("mixed", 9, 2), ("decode", 2, 2)}
    assert common.metric_reader("head_row_share.chat")(_run(et)) == pytest.approx(
        100.0 * 2 / 9)


def _step(t0, kind, wall, phases, admit=None):
    args = {"step": 0, "kind": kind, "tokens": 1}
    if admit:
        args["admit"] = admit
    return E.Step(t0, t0 + wall, args, phases, (t0, t0 + wall))


def _run(et, traced=True, steps=()):
    run = common.Run(workload="w", kind="serve_open", chips=1, cfg=None, traffic={},
                     peaks={}, window=(10.0, 20.0), steps=list(steps))
    run.trace = object() if traced else None
    if et is not None:
        run.extra["engine_trace"] = et
    return run


def _model_step(t0, seconds, *tokens):
    """The benchmark seam's record of one packed step: its grants as
    (slot, start position, tokens) and its span."""
    return {"grants": [(i, 0, n) for i, n in enumerate(tokens)], "t0": t0,
            "t1": t0 + seconds}


READERS = ("host_ms.chat", "host_ms.docs", "decode_step_ms.chat", "sampler_share.chat",
           "admit_pool_blocked_share.chat")


def test_readers_on_a_hand_made_run():
    steps = [
        _step(0.0, "mixed", 0.8, {"admit": 0.01, "dispatch": 0.05, "sync": 0.7,
                                  "sync_overflow": 0.001, "emit": 0.02}, admit="pool"),
        _step(1.0, "decode", 0.1, {"dispatch": 0.01, "sync": 0.06, "sync_overflow": 0.002,
                                   "emit": 0.01}, admit="pool"),
        _step(2.0, "decode", 0.12, {"dispatch": 0.01, "sync": 0.08, "sync_overflow": 0.002,
                                    "emit": 0.01}),
        _step(3.0, "decode", 0.2, {"dispatch": 0.02, "sync": 0.15, "sync_overflow": 0.002,
                                   "emit": 0.01}, admit="slots"),
    ]
    et = E.EngineTrace(busy_s=2.0, module_s={"jit__packed_engine_step": 1.9,
                                             "jit__sampled_tokens": 0.06,
                                             "jit__greedy_tokens": 0.02},
                       steps=steps, idle_gaps=[], decode_device=[])
    model_steps = [
        _model_step(9.0, 0.01, 1, 1),  # before the window
        _model_step(10.0, 0.8, 1, 16), _model_step(11.0, 0.03, 1, 1),
        _model_step(12.0, 0.05, 1), _model_step(13.0, 0.02, 1, 1, 1),
        _model_step(20.0, 0.01, 1),  # after it
    ]
    read = {m: common.metric_reader(m)(_run(et, steps=model_steps)) for m in READERS}
    host = [0.8 - 0.701, 0.1 - 0.062, 0.12 - 0.082, 0.2 - 0.152]
    assert read["host_ms.chat"] == pytest.approx(1e3 * sum(host) / 4)
    assert read["host_ms.docs"] == read["host_ms.chat"]
    # the decode-only steps in the window: 0.03, 0.05, 0.02 s
    assert read["decode_step_ms.chat"] == pytest.approx(30.0)
    assert read["sampler_share.chat"] == pytest.approx(100 * 0.08 / 2.0)
    assert read["admit_pool_blocked_share.chat"] == pytest.approx(50.0)


@pytest.mark.parametrize("case", ["no trace", "no steps", "no decode step"])
def test_readers_with_nothing_to_read(case):
    if case == "no trace":
        run = _run(None, traced=False)
        assert all(common.metric_reader(m)(run) is None for m in READERS)
        assert E.of(run) is None
        return
    steps = [] if case == "no steps" else [
        _step(0.0, "mixed", 0.8, {"dispatch": 0.05, "sync": 0.7, "sync_overflow": 0.0})]
    et = E.EngineTrace(busy_s=1.0, module_s={"jit__packed_engine_step": 0.9}, steps=steps,
                       idle_gaps=[], decode_device=[])
    mixed = [_model_step(11.0, 0.8, 1, 16)] if case == "no decode step" else []
    read = {m: common.metric_reader(m)(_run(et, steps=mixed)) for m in READERS}
    assert read["decode_step_ms.chat"] is None
    assert read["sampler_share.chat"] is None  # no sampler program ran
    if case == "no steps":
        # a trace without the engine's spans, as an engine without them makes
        assert read["host_ms.chat"] is None and read["host_ms.docs"] is None
        assert read["admit_pool_blocked_share.chat"] is None
    else:
        assert read["host_ms.chat"] == pytest.approx(100.0)
        assert read["admit_pool_blocked_share.chat"] == 0.0


def test_of_reads_the_cells_trace_once(tmp_path, monkeypatch):
    """A traced run's reduction comes from the cell's trace directory and
    is kept in the run for the next reader."""
    monkeypatch.setattr(E, "ROOT", str(tmp_path))
    d = tmp_path / "bench_out" / "trace" / "w"
    f = jax.jit(lambda x: x + 1)
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(d))
    with TA("engine:step", step=7) as ann:
        with TA("engine:dispatch"):
            y = f(jnp.ones(4))
        with TA("engine:sync"):
            np.asarray(y)
        ann.set_metadata(kind="decode", tokens=1)
    jax.profiler.stop_trace()
    assert glob.glob(str(d / "**" / "*.xplane.pb"), recursive=True)
    run = _run(None)
    et = E.of(run)
    assert [st.args["step"] for st in et.steps] == [7]
    assert run.extra["engine_trace"] is et and E.of(run) is et
    assert common.metric_reader("host_ms.chat")(run) > 0
