"""Queued serving driver: offline batch work, every request due at once,
driven through the engine's own step loop (no front-end).

The window opens when every request admitted by the first step has
finished, so the engine has left its cold start and mixes prefill with
decode as it does in steady state; it closes at the first step boundary
after ``seconds``.  The queue is sized so that it never runs dry.
"""
from __future__ import annotations

import time

from . import serving, traffic
from .common import Run, log
from .trace import Tracer


def run(ctx) -> Run:
    from repro.serve import Request, SamplingParams

    run, mix, seed = ctx.run, ctx.mix, ctx.seed
    eng, params = serving.build_engine(ctx.cfg, ctx.conf, mix, seed)
    serving.warm_up(eng, mix, ctx.cfg.vocab_size)
    serving.add_seams(eng, run.spans, run.steps)
    reqs = traffic.requests(mix, seed, ctx.seconds, ctx.cfg.vocab_size)
    live = {}
    for q in reqs:
        live[q.uid] = Request(uid=q.uid, prompt=q.prompt, max_new_tokens=q.max_new,
                              sampling=SamplingParams())
        eng.submit(live[q.uid])
    eng.step()
    first_wave = [r for r in live.values() if r.admitted_at is not None]
    while not all(r.finished_at is not None for r in first_wave):
        eng.step()
    tracer = Tracer(ctx.trace_dir, mix["trace_s"]) if ctx.traced else None
    emitted = lambda: sum(len(r.output) for r in live.values())  # noqa: E731
    ctx.compiles.arm()
    t0 = time.perf_counter()
    n0 = emitted()
    while time.perf_counter() - t0 < ctx.seconds:
        if not eng.queue:
            raise RuntimeError("the queue ran dry inside the window; raise the "
                               "mix's queue")
        if tracer is not None and tracer.t0 is None and \
                time.perf_counter() - t0 >= (ctx.seconds - tracer.seconds) / 2:
            tracer.start()
        if tracer is not None and tracer.t1 is None and tracer.t0 is not None and \
                time.perf_counter() - tracer.t0 >= tracer.seconds:
            tracer.stop()
        eng.step()
    t1 = time.perf_counter()
    if tracer is not None and tracer.t1 is None:
        tracer.stop()
    ctx.compiles.disarm()
    run.window = (t0, t1)
    run.setup_s = t0 - ctx.t_start
    run.extra["emitted_tokens"] = emitted() - n0
    run.extra["first_wave"] = len(first_wave)
    run.extra["in_flight"] = sum(not s.free for s in eng.slots)
    if tracer is not None:
        run.trace = tracer.reduce()
        run.extra["trace_window"] = (tracer.t0, tracer.t1)
    run.requests = serving.request_log(reqs, live)
    for r in run.requests:
        fin = live[r["uid"]].finished_at
        r["ok"] = fin is not None and r["n_out"] == r["max_new"]
        r["in_window"] = fin is not None and t0 <= fin <= t1
    in_window = [r for r in run.requests if r["in_window"]]
    run.extra["attempted"] = (len(in_window), sum(not r["ok"] for r in in_window))
    ctx.read_memory()
    eng.cache = eng.kv = None
    del eng
    done = [r for r in run.requests if r["ok"]]
    sample = serving.sample_for_check(done, mix["check"]["requests"], seed)
    measured = serving.check_against_reference(
        params, ctx.workload["config"], ctx.conf["model"], mix, sample)
    run.extra["measured"] = measured
    run.extra["check_input"] = (params, sample)
    run.extra["checks"] = serving.checks_from(measured, mix["check"]["limits"])
    log(f"window: {run.extra['emitted_tokens']} tokens emitted, first wave "
        f"{len(first_wave)} requests, {run.extra['in_flight']} in flight at close")
    return run
