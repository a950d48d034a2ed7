"""Open-loop serving driver: independent users at a fixed rate, through
``repro.serve.AsyncEngine`` over the paged, token-packed engine.

Each request is timed on the client side from the moment it was due:
time to first token runs from the due time to the first token the client
receives, so a late generator or a stalled loop counts against the
system.  The window holds the requests due in ``[0, seconds)``; after it
closes the run waits (up to the mix's ``drain_s``) for them to finish; a
request still unserved when it stops waiting counts at the time it
waited, a lower bound on its latency.
"""
from __future__ import annotations

import asyncio
import time

from . import serving, traffic
from .common import Run, log
from .trace import Tracer


async def _client(fe, q, rec, t0):
    from repro.serve import SamplingParams

    rec["due_at"] = t0 + q.due
    delay = rec["due_at"] - time.perf_counter()
    if delay > 0:
        await asyncio.sleep(delay)
    rec["submitted"] = time.perf_counter()
    stream = await fe.submit(q.prompt, q.max_new, uid=q.uid, sampling=SamplingParams(
        temperature=q.temperature, top_p=q.top_p, seed=q.sample_seed))
    rec["stream"] = stream
    rec["n_recv"] = 0
    async for _ in stream:
        now = time.perf_counter()
        if rec.get("first") is None:
            rec["first"] = now
        rec["last"] = now
        rec["n_recv"] += 1
    rec["status"] = stream.status


async def _drive(eng, reqs, seconds, drain_s, run, tracer):
    from repro.serve import AsyncEngine

    recs = {q.uid: {} for q in reqs}
    async with AsyncEngine(eng, waiting_room=len(reqs) + 1) as fe:
        t0 = time.perf_counter()
        run.window = (t0, t0 + seconds)
        tasks = [asyncio.create_task(_client(fe, q, recs[q.uid], t0)) for q in reqs]
        if tracer is not None:
            tasks.append(asyncio.create_task(tracer.run_async(t0, seconds)))
        done, pending = await asyncio.wait(tasks, timeout=seconds + drain_s)
        run.extra["wait_end"] = time.perf_counter()
        for t in pending:
            t.cancel()
        for t in done:
            t.result()  # surface a client's exception
        await fe.stop(drain=False)
    return recs


def run(ctx) -> Run:
    run, mix, seed = ctx.run, ctx.mix, ctx.seed
    eng, params = serving.build_engine(ctx.cfg, ctx.conf, mix, seed)
    serving.warm_up(eng, mix, ctx.cfg.vocab_size)
    serving.add_seams(eng, run.spans, run.steps)
    reqs = traffic.requests(mix, seed, ctx.seconds, ctx.cfg.vocab_size)
    engine_reqs = {}
    tracer = Tracer(ctx.trace_dir, mix["trace_s"]) if ctx.traced else None
    ctx.compiles.arm()
    recs = asyncio.run(_drive(eng, reqs, ctx.seconds, mix["drain_s"], run, tracer))
    ctx.compiles.disarm()
    run.setup_s = run.window[0] - ctx.t_start
    for q in reqs:
        s = recs[q.uid].get("stream")
        if s is not None:
            engine_reqs[q.uid] = s.request
    log_ = serving.request_log(reqs, engine_reqs)
    for r, q in zip(log_, reqs):
        rec = recs[q.uid]
        r.update(due_at=rec.get("due_at"), submitted=rec.get("submitted"),
                 first=rec.get("first"), last=rec.get("last"),
                 n_recv=rec.get("n_recv", 0),
                 ok=rec.get("status") == "finished" and r["n_out"] == q.max_new)
    run.requests = log_
    run.extra["lateness"] = traffic.lateness(log_)
    run.extra["shared_tokens"] = sum(st.shared_tokens for st in eng.step_stats)
    run.extra["attempted"] = (len(log_), sum(not r["ok"] for r in log_))
    if tracer is not None:
        run.trace = tracer.reduce()
        run.extra["trace_window"] = (tracer.t0, tracer.t1)
    ctx.read_memory()
    eng.cache = eng.kv = None
    del eng
    done = [r for r in log_ if r["ok"]]
    sample = serving.sample_for_check(done, mix["check"]["requests"], seed)
    measured = serving.check_against_reference(
        params, ctx.workload["config"], ctx.conf["model"], mix, sample)
    run.extra["measured"] = measured
    run.extra["check_input"] = (params, sample)
    run.extra["checks"] = serving.checks_from(measured, mix["check"]["limits"])
    log(f"generator lateness: {run.extra['lateness']}")
    return run
