"""Readings that set the limits of a cell's ``correct``: the program's,
the control's and the planted faults', over several seeds in one process.

The control is the plain reference computed in the precision below the
configuration's (float8 e4m3 inputs to every matrix product, for
bfloat16), put in the program's place.  Each seed runs the cell's timed
path (a short window) with its check, which gives the program's numbers.
On the first ``--control-seeds`` seeds it also reads, for a served cell,
the control's widest gap on the same served requests, and for a training
cell the control and the faults: the reference with the fault planted
(half of the batch left out, the mean over the rest; the exchange between
chips left out, so chip 0's gradient alone), each against the float32
reference.

    python bench/control.py --workload <cell> --seconds 20 --seeds 11,12,13

``--rehearsal`` reads the same numbers on the CPU at the rehearsal sizes,
with the Pallas paged kernel interpreted: readings for the tests, never
for the limits of a cell on the chip.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run as bench_run  # noqa: E402
from bench import common  # noqa: E402


def _drive(args, seed):
    """One run of the cell's timed path (a short window) with its check;
    the run's record."""
    import importlib

    workload, conf, mix, devices = bench_run.prepare(args, args.rehearsal)
    cfg = bench_run.model_config(conf)
    run = common.Run(workload=workload["name"], kind=mix["driver"],
                     chips=workload["chips"], cfg=cfg, traffic=mix, peaks={},
                     spans=common.Spans(False))
    ctx = bench_run.Ctx(workload=workload, conf=conf, mix=mix, cfg=cfg, seed=seed,
                        seconds=args.seconds, traced=False, trace_dir="",
                        t_start=time.perf_counter(), run=run,
                        compiles=bench_run.CompileCounter(),
                        devices=devices[: workload["chips"]])
    importlib.import_module("bench." + mix["driver"]).run(ctx)
    return workload, conf, mix, cfg, run


def serve_readings(args, seed, control):
    from bench import serving

    workload, conf, mix, _, run = _drive(args, seed)
    out = dict(run.extra["measured"])
    params, sample = run.extra.pop("check_input")
    if control:
        ctl = serving.control_gaps(params, workload["config"], conf["model"], mix, sample)
        out["control_gap_std"] = ctl["gap_std"]
        out["control_mean_gap_std"] = ctl["mean_gap_std"]
    return out


def train_readings(args, seed, control):
    import jax
    import numpy as np

    from repro.core import LatencyModel, NoiseModel
    from repro.data import DataConfig
    from repro.data.synthetic import batch_at

    from bench.train_spmd import gaps
    from bench.weights import make_params

    workload, conf, job, cfg, run = _drive(args, seed)
    out = {"program": {k: v for k, (v, _) in run.extra["checks"].items()}}
    if not control:
        return out
    ref = common.reference_module(workload["config"])
    rows = job["workers"] * job["microbatches"] * job["rows"]
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=job["seq_len"],
                      batch_size=rows, seed=seed)
    lat = LatencyModel(base=job["latency"]["base"],
                       noise=NoiseModel(kind=job["latency"]["noise"]))
    steps = range(job["check_steps"])
    batches = [batch_at(s, data) for s in steps]
    lats = [lat.sample_at(s, job["workers"], job["microbatches"], seed=seed + 1) for s in steps]
    p0 = make_params(cfg, conf, seed)
    devs = jax.devices()[: workload["chips"]]

    def follow(quant=None, keep_rows=None):
        bs = batches
        if keep_rows is not None:
            bs = [{**b, "weights": b["weights"] * (np.arange(rows) < keep_rows)[:, None]}
                  for b in batches]
        return ref.train_steps(p0, bs, lats, job, cfg.n_heads, devs, quant=quant)

    base = follow()
    out["reference_losses"] = base[0]
    for name, kw in (("control_fp8", {"quant": "fp8"}),
                     ("fault_half_batch", {"keep_rows": rows // 2}),
                     ("fault_no_exchange", {"keep_rows": rows // job["workers"]})):
        losses, g, d = follow(**kw)
        out[name] = {
            "loss_rel": max(abs(a - b) / abs(b) for a, b in zip(losses, base[0])),
            "grad_gap": gaps(g, base[1])[0], "update_gap": gaps(d, base[2])[0]}
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="read the control (and a training cell's faults) on "
                         "the first this many seeds; the program on all")
    ap.add_argument("--rehearsal", action="store_true",
                    help="on the CPU at the rehearsal sizes (not for limits)")
    args = ap.parse_args()
    if args.rehearsal:
        from bench import rehearse

        rehearse.interpret_paged_kernel()
    _, _, mix = common.cell(args.workload)
    fn = train_readings if mix["driver"] == "train_spmd" else serve_readings
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = fn(args, seed, control=i < args.control_seeds)
        print(json.dumps({"workload": args.workload, "seed": seed, **out,
                          "seconds": time.perf_counter() - t0}), flush=True)


if __name__ == "__main__":
    main()
