"""Pieces every cell shares: the benchmark's files, seeds, host spans, the
run record that metric readers take, and the result line."""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


def cell(name: str) -> Tuple[dict, dict, dict]:
    """(workload entry, configuration file, traffic file) of one cell."""
    bench = benchmark()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(by_name)}")
    w = by_name[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return w, load_json(ROOT, conf["file"]), load_json(BENCH_DIR, "traffic",
                                                       w["traffic"] + ".json")


def cell_metrics(name: str, per_layer: bool) -> List[dict]:
    """The metrics a cell reports: end-to-end (``--trace 0``) or per-layer
    (``--trace 1``), filtered by each metric's ``workloads`` list."""
    bench = benchmark()
    group = bench["per_layer"] if per_layer else bench["end_to_end"]
    return [m for m in group if name in m.get("workloads", [name])]


def metric_reader(name: str):
    """``bench/metrics/<name>.py``'s ``read(run)``."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference_module(config_name: str):
    """``bench/configs/<config>_ref.py``: the plain reference of a config."""
    path = os.path.join(BENCH_DIR, "configs", config_name + "_ref.py")
    spec = importlib.util.spec_from_file_location("bench_ref_" + config_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seed_key(seed: int):
    """A JAX key from a seed of any size (the driver's exceed 32 bits)."""
    import jax

    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def rng(seed: int, *stream: int):
    import numpy as np

    return np.random.default_rng(np.random.SeedSequence([seed, *stream]))


class Spans:
    """Host spans around the calls into each layer, on the host clock.

    In a traced run each span is also a ``TraceAnnotation`` named
    ``bench:<name>``, so the trace reduction can say what the host was
    doing in each idle gap of the device.
    """

    def __init__(self, traced: bool):
        self.traced = traced
        self.records: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.traced:
            import jax

            ann = jax.profiler.TraceAnnotation("bench:" + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.records.append((name, t0, t1))

    def begin(self, name: str):
        """Open a span that a later ``end`` closes (one not nested in a
        ``with`` block, such as the trainer's time between steps)."""
        ann = None
        if self.traced:
            import jax

            ann = jax.profiler.TraceAnnotation("bench:" + name)
            ann.__enter__()
        return name, ann, time.perf_counter()

    def end(self, token) -> None:
        name, ann, t0 = token
        t1 = time.perf_counter()
        if ann is not None:
            ann.__exit__(None, None, None)
        self.records.append((name, t0, t1))


@dataclasses.dataclass
class Run:
    """Everything one run measured; the metric readers take this."""

    workload: str
    kind: str
    chips: int
    cfg: Any  # the program's ModelConfig
    traffic: dict
    peaks: dict
    setup_s: float = float("nan")
    window: Tuple[float, float] = (float("nan"), float("nan"))  # host clock
    spans: Optional[Spans] = None
    requests: List[dict] = dataclasses.field(default_factory=list)
    steps: List[dict] = dataclasses.field(default_factory=list)
    trace: Any = None  # bench.trace.Reduction of the traced sub-window
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation; inf entries
    (failed requests) sort beyond every finite one."""
    import numpy as np

    vals = np.sort(np.asarray(values, np.float64))
    if vals.size == 0:
        return float("nan")
    pos = q / 100.0 * (vals.size - 1)
    lo, hi = int(np.floor(pos)), int(np.ceil(pos))
    if not np.isfinite(vals[hi]):
        return float("inf")
    return float(vals[lo] + (vals[hi] - vals[lo]) * (pos - lo))


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def device_block(devices, count: int) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": count}


def finite(x: float) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
