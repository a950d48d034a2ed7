"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns an ``.xplane.pb`` into plain events; ``reduce`` computes,
from the device operations alone: the busy time of each device (the union
of its operation intervals), the time of each operation, the part of the
collectives during which no other operation runs on that device, and the
idle gaps, each labelled with the innermost ``bench:`` host span that
covers it.  The reduction is plain Python on plain events, so the tests
check it on a small recorded trace.
"""
from __future__ import annotations

import asyncio
import collections
import dataclasses
import glob
import os
import shutil
import time
from typing import Dict, List, Optional, Tuple

HOST_PREFIX = "bench:"


@dataclasses.dataclass
class Event:
    device: Optional[str]  # None = a host event
    name: str
    start: float  # seconds, on the trace's clock
    end: float


def load(path: str) -> List[Event]:
    """Device operations (the ``XLA Ops`` line of each TPU plane, or, on
    a CPU trace, events that carry an ``hlo_op`` stat) and the host's
    ``bench:`` annotations."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:") and "TPU" in plane.name
        for line in plane.lines:
            for e in line.events:
                s, d = e.start_ns * 1e-9, e.duration_ns * 1e-9
                if is_dev:
                    if line.name == "XLA Ops":
                        out.append(Event(plane.name, e.name, s, s + d))
                elif e.name.startswith(HOST_PREFIX):
                    out.append(Event(None, e.name[len(HOST_PREFIX):], s, s + d))
                elif not plane.name.startswith("/device:"):
                    stats = dict(e.stats)
                    if "hlo_op" in stats:
                        out.append(Event(f"cpu:{stats.get('device_ordinal', 0)}",
                                         e.name, s, s + d))
    return out


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: List[Tuple[float, float]], b: List[Tuple[float, float]]):
    """Parts of the union ``a`` not covered by the union ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


COLLECTIVE = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
              "all-to-all")


def is_collective(name: str) -> bool:
    return any(c in name for c in COLLECTIVE)


@dataclasses.dataclass
class Reduction:
    window_s: float
    devices: List[str]
    busy_s: float  # mean over devices
    op_s: Dict[str, float]  # per-device mean seconds of each operation name
    collective_s: float  # mean over devices
    exposed_collective_s: float  # mean over devices
    idle_gaps: List[Tuple[str, float]]  # longest gaps of the first device
    n_host_spans: Dict[str, int]

    def kernel_s(self, fragment: str) -> Optional[float]:
        """Per-device seconds of the operations whose name holds
        ``fragment``; None when none ran."""
        hits = [s for n, s in self.op_s.items() if fragment in n]
        return sum(hits) if hits else None

    def breakdown(self) -> dict:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:10]]}


def reduce(events: List[Event], window_s: float) -> Reduction:
    ops = [e for e in events if e.device is not None]
    host = [e for e in events if e.device is None]
    devices = sorted({e.device for e in ops})
    busy, coll, exposed, op_s = [], [], [], collections.Counter()
    per_dev_union = {}
    for d in devices:
        evs = [e for e in ops if e.device == d]
        u = union([(e.start, e.end) for e in evs])
        per_dev_union[d] = u
        busy.append(length(u))
        c = union([(e.start, e.end) for e in evs if is_collective(e.name)])
        other = union([(e.start, e.end) for e in evs if not is_collective(e.name)])
        coll.append(length(c))
        exposed.append(length(subtract(c, other)))
        for e in evs:
            op_s[e.name] += e.end - e.start
    n = max(len(devices), 1)
    gaps = []
    if devices:
        u = per_dev_union[devices[0]]
        for (_, a), (b, _) in zip(u, u[1:]):
            mid = 0.5 * (a + b)
            cover = [h for h in host if h.start <= mid <= h.end]
            label = min(cover, key=lambda h: h.end - h.start).name if cover else "no span"
            gaps.append((label, b - a))
    gaps.sort(key=lambda g: -g[1])
    return Reduction(
        window_s=window_s, devices=devices,
        busy_s=sum(busy) / n,
        op_s={k: v / n for k, v in op_s.items()},
        collective_s=sum(coll) / n, exposed_collective_s=sum(exposed) / n,
        idle_gaps=gaps, n_host_spans=dict(collections.Counter(h.name for h in host)),
    )


class Tracer:
    """Profiles one sub-window of a run into ``trace_dir``."""

    def __init__(self, trace_dir: str, seconds: float):
        self.dir = trace_dir
        self.seconds = seconds
        self.t0 = self.t1 = None

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.perf_counter()

    def stop(self):
        import jax

        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()

    async def run_async(self, w0: float, window_s: float):
        """Trace the middle ``seconds`` of the window that opens at ``w0``."""
        await asyncio.sleep(max(w0 + (window_s - self.seconds) / 2 - time.perf_counter(), 0))
        self.start()
        await asyncio.sleep(self.seconds)
        self.stop()

    def reduce(self) -> Reduction:
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True)
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        return reduce(load(max(files, key=os.path.getmtime)), self.t1 - self.t0)
