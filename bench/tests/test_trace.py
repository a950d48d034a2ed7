"""The trace reduction on hand-made events and on a trace recorded here."""
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from bench import trace as T


def ev(dev, name, s, e):
    return T.Event(dev, name, s, e)


def test_busy_collectives_and_gaps_on_one_device():
    r = T.reduce([
        ev("d0", "fusion.1", 0.0, 2.0), ev("d0", "fusion.2", 1.0, 3.0),
        ev("d0", "all-reduce.1", 2.5, 5.0), ev("d0", "fusion.3", 6.0, 7.0),
        ev("d0", "fusion.4", 7.5, 8.0),
        ev(None, "step", 0.0, 10.0), ev(None, "model_step", 4.0, 7.2),
    ], window_s=10.0)
    assert r.busy_s == pytest.approx(6.5)  # [0, 5] + [6, 7] + [7.5, 8]
    assert r.collective_s == pytest.approx(2.5)
    assert r.exposed_collective_s == pytest.approx(2.0)  # [3, 5]
    # the gap [5, 6] lies in both spans: the innermost names it
    assert r.idle_gaps == [("model_step", pytest.approx(1.0)), ("step", pytest.approx(0.5))]
    assert r.op_s["fusion.2"] == pytest.approx(2.0)
    assert r.kernel_s("fusion") == pytest.approx(5.5)
    assert r.kernel_s("paged") is None


def test_means_over_devices():
    r = T.reduce([
        ev("d0", "fusion.1", 0.0, 4.0), ev("d1", "fusion.1", 0.0, 2.0),
        ev("d1", "all-reduce.7", 2.0, 3.0),
    ], window_s=4.0)
    assert r.devices == ["d0", "d1"]
    assert r.busy_s == pytest.approx(3.5)
    assert r.op_s["fusion.1"] == pytest.approx(3.0)
    assert r.exposed_collective_s == pytest.approx(0.5)
    assert r.breakdown()["device_ops"][0] == ["fusion.1", pytest.approx(3.0)]


def test_subtract_and_union():
    assert T.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert T.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [(0, 1), (2, 4), (6, 9)]


def test_recorded_trace(tmp_path):
    """A trace the profiler records on this host: the reduction finds the
    program's operations and the benchmark's host span."""
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    tracer = T.Tracer(str(tmp_path / "trace"), 0)
    tracer.start()
    with jax.profiler.TraceAnnotation("bench:step"):
        for _ in range(3):
            f(x).block_until_ready()
    tracer.stop()
    files = glob.glob(os.path.join(tracer.dir, "**", "*.xplane.pb"), recursive=True)
    events = T.load(files[0])
    assert any(e.device is None and e.name == "step" for e in events)
    r = tracer.reduce()
    assert r.busy_s > 0 and r.busy_s <= r.window_s
    assert r.n_host_spans.get("step") == 1
    assert any("dot" in n for n in r.op_s)
