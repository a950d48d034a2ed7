"""Sweep the offered rate of an open-loop serving cell on the chip, in one
process over one warm engine, to find the knee: the highest rate at
which the tails hold and no backlog builds.  Run once when a cell is
defined; the cell then offers a fixed rate (its mix's ``rate_rps``).

    python bench/sweep.py --workload internlm2_1_8b-chat --seed 11 \
        --seconds 20 --rates 3,4,5,6,7,8
"""
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402

from bench import run as bench_run  # noqa: E402
from bench import common, serve_open, serving, traffic  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--drain", type=float, default=None,
                    help="seconds to wait after each window (default: the mix's)")
    args = ap.parse_args()
    workload, conf, mix, devices = bench_run.prepare(args)
    cfg = bench_run.model_config(conf)
    eng, _ = serving.build_engine(cfg, conf, mix, args.seed)
    serving.warm_up(eng, mix, cfg.vocab_size)
    ttft = common.metric_reader("ttft_p95_ms")
    tpot = common.metric_reader("tpot_p95_ms")
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        m = {**mix, "rate_rps": rate}
        reqs = traffic.requests(m, args.seed + i, args.seconds, cfg.vocab_size)
        run = common.Run(workload=workload["name"], kind="sweep", chips=1, cfg=cfg,
                         traffic=m, peaks={})
        recs = asyncio.run(serve_open._drive(eng, reqs, args.seconds, args.drain or m["drain_s"], run, None))
        for q in reqs:
            rec = recs[q.uid]
            run.requests.append({
                "due_at": rec.get("due_at"), "submitted": rec.get("submitted"),
                "first": rec.get("first"), "last": rec.get("last"),
                "n_recv": rec.get("n_recv", 0),
                "ok": rec.get("status") == "finished"})
        drain = max((r["last"] or 0) for r in run.requests) - run.window[1]
        print(json.dumps({
            "rate_rps": rate, "requests": len(reqs),
            "failed": sum(not r["ok"] for r in run.requests),
            "ttft_p95_ms": ttft(run), "tpot_p95_ms": tpot(run),
            "drain_after_window_s": drain,
            "generator_lateness": traffic.lateness(run.requests)}), flush=True)
        eng.reset_stats()


if __name__ == "__main__":
    main()
