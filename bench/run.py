"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``, with its
plain reference ``<config>_ref.py`` beside it) and a traffic mix or job
(``bench/traffic/<mix>.json``), whose ``driver`` names the module that
runs it.  Set-up (imports, device, weights from the seed, compilation or
the compile cache, warm-up of the cell's own shapes) runs from process
start to the window; the window measures ``--seconds``; then the outputs
of the timed path are checked against the reference.  With ``--trace 0``
the line carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics (``bench/metrics/<metric>.py`` each), read from a
profiled sub-window.  The last line of standard output is one JSON object;
the numbers the check compared, each beside its limit, close both it and
standard error.  A host where JAX finds no TPU, or fewer chips than the
cell asks for, exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import common  # noqa: E402
from bench.common import log  # noqa: E402

OUT_DIR = os.path.join(ROOT, "bench_out")


class CompileCounter:
    """Counts programs compiled (or loaded from the cache) while armed."""

    def __init__(self):
        import jax

        self.armed, self.n, self.names = False, 0, []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.names.append(kw.get("fun_name", "?"))

    def arm(self):
        self.armed = True

    def disarm(self):
        self.armed = False


@dataclasses.dataclass
class Ctx:
    workload: dict
    conf: dict
    mix: dict
    cfg: object
    seed: int
    seconds: float
    traced: bool
    trace_dir: str
    t_start: float
    run: common.Run
    compiles: CompileCounter
    devices: list
    memory_peak: int = 0

    def read_memory(self):
        self.memory_peak = common.memory_peak_bytes(self.devices)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def model_config(conf: dict):
    from repro.models import ModelConfig

    return ModelConfig(**conf["model"]).validate()


def prepare(args, rehearsal: bool = False):
    """Device, compile cache and the cell's files; exits where the host
    lacks the chips the cell asks for."""
    import jax

    from repro.launch import compile_cache

    workload, conf, mix = common.cell(args.workload)
    if rehearsal:
        mix = {**mix, **mix.get("rehearsal", {})}
        conf = {**conf, "model": {**conf["model"], **conf["rehearsal"]["model"]}}
    else:
        compile_cache.enable()
        # cache every program, however fast it compiled: a warm run then
        # loads everything and compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"no accelerator found: {e}")
    if not rehearsal and devices[0].platform != "tpu":
        raise SystemExit(f"no TPU found: JAX sees only {devices[0].platform} devices")
    if len(devices) < workload["chips"]:
        raise SystemExit(f"{workload['chips']} chips wanted, {len(devices)} found")
    return workload, conf, mix, devices


def execute(args, rehearsal: bool = False) -> dict:
    workload, conf, mix, devices = prepare(args, rehearsal)
    from bench.peaks import peaks_for

    peaks = peaks_for("TPU v5 lite" if rehearsal else devices[0].device_kind)
    cfg = model_config(conf)
    run = common.Run(workload=workload["name"], kind=mix["driver"],
                     chips=workload["chips"], cfg=cfg, traffic=mix, peaks=peaks,
                     spans=common.Spans(bool(args.trace)))
    run.extra["model"] = conf["model"]
    ctx = Ctx(workload=workload, conf=conf, mix=mix, cfg=cfg, seed=args.seed,
              seconds=args.seconds, traced=bool(args.trace),
              trace_dir=os.path.join(OUT_DIR, "trace", workload["name"]),
              t_start=T_START, run=run, compiles=CompileCounter(),
              devices=devices[: workload["chips"]])
    driver = importlib.import_module("bench." + mix["driver"])
    driver.run(ctx)

    checks = run.extra.get("checks", {})
    correct = bool(checks) and all(
        common.finite(v) and v <= lim for v, lim in checks.values())
    if ctx.compiles.n:
        log(f"{ctx.compiles.n} program(s) compiled inside the window: "
            f"{sorted(set(ctx.compiles.names))}")
    metrics = {}
    for m in common.cell_metrics(workload["name"], per_layer=bool(args.trace)):
        value = common.metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    attempted, failed = run.extra.get("attempted", (0, 0))
    device = common.device_block(devices, len(devices))
    device["memory_peak_bytes"] = ctx.memory_peak
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    result["compiles_in_window"] = ctx.compiles.n
    if "lateness" in run.extra:
        result["generator_lateness_ms"] = run.extra["lateness"]
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v!r} (limit {lim!r})")
    return result


def main(argv=None) -> None:
    args = parse(argv)
    try:
        result = execute(args)
    except SystemExit:
        raise
    except Exception:
        traceback.print_exc()
        raise SystemExit(1)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
