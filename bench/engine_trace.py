"""The serving engine's own spans in a profiler trace, and the jitted
program each device operation ran in.

``load`` reads an ``.xplane.pb`` into host spans (the annotations the
program makes, ``engine:<phase>`` inside ``ContinuousBatcher.step`` and
``frontend:<phase>`` in ``AsyncEngine``'s loop, under their full names
and with their arguments) and device operations, each with the name of
its jitted program: the ``hlo_module`` stat where the operation carries
one (a CPU trace's all do), else the ``XLA Modules`` event of its TPU
plane that holds it.
``reduce`` turns them into the engine's steps with their phases, the
device seconds of each program, and the idle gaps of the device, each
labelled by the innermost span that covers it.

``bench/trace.py`` reduces the same trace for the metrics that read the
benchmark's own ``bench:`` spans; this module is what the metrics of the
engine's spans read (``of``).  Run on a trace it prints its reduction::

    python -m bench.engine_trace bench_out/trace/<cell>
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import json
import os
import re
import statistics
import sys
from typing import Dict, List, Optional, Tuple

from .common import ROOT
from .trace import length, union

PREFIXES = ("engine:", "frontend:")
SYNC_PHASES = ("sync", "sync_overflow")
DEVICE_PHASES = ("dispatch",) + SYNC_PHASES
N_GAPS = 20  # idle gaps labelled, longest first


@dataclasses.dataclass
class Span:
    name: str  # full name, "engine:dispatch"
    start: float  # seconds, on the trace's clock
    end: float
    args: dict


@dataclasses.dataclass
class Op:
    device: str
    module: str  # the jitted program's name, "jit__sampled_tokens"
    start: float
    end: float


@dataclasses.dataclass
class Step:
    """One ``engine:step`` span with the phase spans inside it."""

    start: float
    end: float
    args: dict  # step, kind, tokens and, where admission stopped, admit
    phases: Dict[str, float]  # seconds by phase name
    #: from the start of ``dispatch`` to the end of the last sync
    device_window: Optional[Tuple[float, float]] = None

    @property
    def kind(self) -> Optional[str]:
        return self.args.get("kind")

    @property
    def wall_s(self) -> float:
        return self.end - self.start

    @property
    def host_s(self) -> float:
        """The step less its waits on the device."""
        return self.wall_s - sum(self.phases.get(p, 0.0) for p in SYNC_PHASES)


@dataclasses.dataclass
class EngineTrace:
    busy_s: float  # mean over devices
    module_s: Dict[str, float]  # per-device mean device seconds of each program
    steps: List[Step]
    idle_gaps: List[Tuple[str, float]]  # the longest of the first device, longest first
    #: for each decode step: its ``device_window``'s seconds and the
    #: device's busy seconds inside it
    decode_device: List[Tuple[float, float]]

    def modules_s(self, fragments) -> Optional[float]:
        """Device seconds of the programs whose name holds one of
        ``fragments``; None when none ran."""
        hits = [s for m, s in self.module_s.items() if any(f in m for f in fragments)]
        return sum(hits) if hits else None


_SUFFIX = re.compile(r"\(\d+\)$")


def _module_name(name: str) -> str:
    """``jit__sampled_tokens(42)`` -> ``jit__sampled_tokens``."""
    return _SUFFIX.sub("", name)


def _in_modules(ops: List[Tuple[float, float]],
                modules: List[Tuple[float, float, str]]) -> List[str]:
    """The module whose interval holds each operation's midpoint."""
    modules = sorted(modules)
    starts = [m[0] for m in modules]
    out = []
    for s, e in ops:
        k = bisect.bisect_right(starts, 0.5 * (s + e)) - 1
        out.append(modules[k][2] if k >= 0 and modules[k][1] >= 0.5 * (s + e) else "?")
    return out


def load(path: str) -> Tuple[List[Span], List[Op]]:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, ops = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = {line.name: list(line.events) for line in plane.lines}
            evs = lines.get("XLA Ops", [])
            raw = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                   for e in evs]
            mods = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                     _module_name(e.name)) for e in lines.get("XLA Modules", [])]
            for e, (s, t), m in zip(evs, raw, _in_modules(raw, mods)):
                m = _module_name(str(dict(e.stats).get("hlo_module", m)))
                ops.append(Op(plane.name, m, s, t))
            continue
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                s, t = e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9
                if e.name.startswith(PREFIXES):
                    spans.append(Span(e.name, s, t, dict(e.stats)))
                    continue
                stats = dict(e.stats)
                if "hlo_op" in stats:
                    ops.append(Op(f"cpu:{stats.get('device_ordinal', 0)}",
                                  _module_name(str(stats.get("hlo_module", "?"))), s, t))
    return spans, ops


def steps_of(spans: List[Span]) -> List[Step]:
    """Each ``engine:step`` span with the ``engine:`` phases inside it."""
    phases = sorted((s for s in spans if s.name.startswith("engine:")
                     and s.name != "engine:step"), key=lambda s: s.start)
    starts = [p.start for p in phases]
    out = []
    for st in sorted((s for s in spans if s.name == "engine:step"), key=lambda s: s.start):
        ph, window = collections.Counter(), []
        for p in phases[bisect.bisect_left(starts, st.start):]:
            if p.start > st.end:
                break
            if p.end <= st.end:
                name = p.name.partition(":")[2]
                ph[name] += p.end - p.start
                if name in DEVICE_PHASES:
                    window.append(p)
        out.append(Step(st.start, st.end, st.args, dict(ph), (
            min(p.start for p in window), max(p.end for p in window)) if window else None))
    return out


def _busy_within(u: List[Tuple[float, float]], a: float, b: float) -> float:
    return sum(max(0.0, min(e, b) - max(s, a)) for s, e in u)


def reduce(spans: List[Span], ops: List[Op]) -> EngineTrace:
    devices = sorted({o.device for o in ops})
    n = max(len(devices), 1)
    module_s, busy = collections.Counter(), []
    unions = {}
    for d in devices:
        by_module = collections.defaultdict(list)
        for o in ops:
            if o.device == d:
                by_module[o.module].append((o.start, o.end))
        unions[d] = union([iv for ivs in by_module.values() for iv in ivs])
        busy.append(length(unions[d]))
        for m, ivs in by_module.items():
            # a union: a TPU loop's operation spans the operations inside it
            module_s[m] += length(union(ivs))
    steps = steps_of(spans)
    gaps, decode_device = [], []
    if devices:
        u = unions[devices[0]]
        longest = sorted(((b - a, a, b) for (_, a), (b, _) in zip(u, u[1:])),
                         reverse=True)[:N_GAPS]
        for gap, a, b in longest:
            mid = 0.5 * (a + b)
            cover = [s for s in spans if s.start <= mid <= s.end]
            label = min(cover, key=lambda s: s.end - s.start).name if cover else "no span"
            gaps.append((label, gap))
        decode_device = [(st.device_window[1] - st.device_window[0],
                          _busy_within(u, *st.device_window)) for st in steps
                         if st.kind == "decode" and st.device_window is not None]
    return EngineTrace(busy_s=sum(busy) / n,
                       module_s={k: v / n for k, v in module_s.items()},
                       steps=steps, idle_gaps=gaps, decode_device=decode_device)


def trace_file(directory: str) -> Optional[str]:
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def of(run) -> Optional[EngineTrace]:
    """The engine trace of a traced run, read once and kept in
    ``run.extra["engine_trace"]``; None for a run without a trace.  The
    trace is the one ``bench/run.py`` had the profiler write for the
    run's cell, under ``bench_out/trace/<cell>``."""
    if "engine_trace" not in run.extra:
        path = None
        if run.trace is not None:
            path = trace_file(os.path.join(ROOT, "bench_out", "trace", run.workload))
        run.extra["engine_trace"] = reduce(*load(path)) if path else None
    return run.extra["engine_trace"]


def summary(et: EngineTrace) -> dict:
    """What the metrics of the engine's spans read, and the figures
    behind them."""
    kinds = collections.Counter(st.kind for st in et.steps)
    decode = [st for st in et.steps if st.kind == "decode"]
    med = lambda xs: statistics.median(xs) if xs else None  # noqa: E731
    return {
        "busy_s": et.busy_s,
        "steps": dict(kinds),
        "admit": dict(collections.Counter(st.args.get("admit") for st in et.steps)),
        "host_ms_mean": 1e3 * statistics.fmean([st.host_s for st in et.steps])
        if et.steps else None,
        "phase_ms_mean": {p: 1e3 * statistics.fmean([st.phases.get(p, 0.0) for st in et.steps])
                          for p in sorted({p for st in et.steps for p in st.phases})},
        "decode_steps": len(decode),
        # dispatch to results against the device's busy time inside:
        # the difference is launch and transfer latency
        "decode_span_ms_median": med([1e3 * w for w, _ in et.decode_device]),
        "decode_busy_ms_median": med([1e3 * b for _, b in et.decode_device]),
        "decode_latency_ms_median": med([1e3 * (w - b) for w, b in et.decode_device]),
        "modules_s": dict(sorted(et.module_s.items(), key=lambda kv: -kv[1])[:10]),
        "idle_gaps": et.idle_gaps[:10],
    }


def main(argv=None) -> None:
    target = (argv or sys.argv[1:])[0]
    path = trace_file(target) if os.path.isdir(target) else target
    if path is None:
        raise SystemExit(f"no .xplane.pb under {target}")
    print(json.dumps(summary(reduce(*load(path))), indent=1))


if __name__ == "__main__":
    main()
