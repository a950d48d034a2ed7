"""Async serving front-end: parity with the synchronous driver,
lifecycle events, layered backpressure, and the TTFT accounting split.

The acceptance bar is the parity class: the exact token streams the
synchronous ``run()`` driver produces must come back through
``AsyncEngine`` streams — dense and paged — no matter how arrivals
interleave with steps.  Everything async adds (waiting room, queue
timeout, deadline drops, cancellation) must shed load *explicitly*:
every submitted request ends in exactly one of
finished/dropped/cancelled/rejected, and a paged engine ends every test
with zero referenced pages.

All asyncio plumbing goes through ``asyncio.run`` — no async test
framework needed.  Determinism note: a coroutine only yields to the
event loop at an *actual* await point, and ``AsyncEngine.submit`` has
none — so back-to-back submits run atomically with respect to the
driver task, which is what makes the waiting-room overflow tests exact
rather than racy.
"""
import asyncio
import dataclasses

import jax
import numpy as np
import pytest

from repro.models import ModelConfig
from repro.models.model import init_params
from repro.serve import (
    AdmissionError,
    AsyncEngine,
    ContinuousBatcher,
    InvalidRequestError,
    Request,
    StepStats,
)

CFG = ModelConfig(
    name="serve-fe-t", n_layers=2, d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
    vocab_size=101, layer_pattern="LG", sliding_window=6, dtype="float32",
    remat=False,
)

PROMPT_LENS = (3, 5, 12, 4, 8, 6)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def make_prompts(seed=0, lens=PROMPT_LENS):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, size=n).tolist() for n in lens]


def make_engine(params, **kw):
    kw.setdefault("batch_slots", 2)
    kw.setdefault("max_len", 24)
    kw.setdefault("chunk_size", 4)
    return ContinuousBatcher(params, CFG, **kw)


def sync_outputs(params, prompts, max_new=4, **kw):
    eng = make_engine(params, **kw)
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=list(p), max_new_tokens=max_new))
    eng.run()
    return {u: r.output for u, r in eng.finished.items()}


async def async_outputs(eng, prompts, max_new=4, **fe_kw):
    async with AsyncEngine(eng, **fe_kw) as fe:
        streams = [await fe.submit(p, max_new) for p in prompts]
        outs = await asyncio.gather(*(s.collect() for s in streams))
    assert all(s.status == "finished" for s in streams)
    return {s.uid: out for s, out in zip(streams, outs)}, streams


# ---------------------------------------------------------------------------
# Parity: async streams == synchronous driver
# ---------------------------------------------------------------------------


class TestSyncParity:
    @pytest.mark.parametrize("cache,packed", [("dense", False),
                                              ("paged", True)])
    def test_streams_token_identical(self, params, cache, packed):
        """The acceptance criterion: submitting through the async
        front-end yields byte-identical output streams to the
        synchronous run() driver, dense and paged."""
        prompts = make_prompts()
        want = sync_outputs(params, prompts)
        kw = dict(cache=cache, packed=packed)
        if cache == "paged":
            kw["page_size"] = 8
        eng = make_engine(params, **kw)
        got, _ = asyncio.run(async_outputs(eng, prompts))
        assert got == want
        if eng.kv is not None:
            assert eng.kv.tables.used_pages == 0
            eng.kv.check_invariants()

    def test_staggered_arrivals_same_streams(self, params):
        """Arrivals interleaved with steps (sleeps between submits)
        still produce the same per-request streams — per-slot KV
        isolation makes greedy outputs schedule-independent."""
        prompts = make_prompts(seed=3)
        want = sync_outputs(params, prompts)

        async def go():
            eng = make_engine(params)
            async with AsyncEngine(eng) as fe:
                streams = []
                for p in prompts:
                    streams.append(await fe.submit(p, 4))
                    await asyncio.sleep(0.01)  # let steps interleave
                await asyncio.gather(*(s.collect() for s in streams))
            return {s.uid: s.tokens for s in streams}

        assert asyncio.run(go()) == want

    def test_tokens_stream_incrementally(self, params):
        """__anext__ yields tokens one at a time, in generation order,
        matching the request's final output."""

        async def go():
            eng = make_engine(params, batch_slots=1)
            async with AsyncEngine(eng) as fe:
                stream = await fe.submit(make_prompts()[0], 6)
                seen = [tok async for tok in stream]
            assert seen == stream.request.output and len(seen) == 6
            return stream

        stream = asyncio.run(go())
        assert stream.status == "finished"


# ---------------------------------------------------------------------------
# Lifecycle events
# ---------------------------------------------------------------------------


class TestLifecycle:
    def test_event_order_and_timestamps(self, params):
        async def go():
            eng = make_engine(params)
            async with AsyncEngine(eng) as fe:
                stream = await fe.submit(make_prompts()[2], 4)
                await stream.collect()
            return stream

        stream = asyncio.run(go())
        kinds = [e.kind for e in stream.events]
        assert kinds == ["queued", "admitted", "first_token", "finished"]
        times = [e.time for e in stream.events]
        assert times == sorted(times)
        r = stream.request
        assert stream.events[0].time == r.submitted_at
        assert stream.events[1].time == r.admitted_at
        assert stream.events[2].time == r.first_token_at

    def test_truncation_surfaces_in_finish_event(self, params):
        """validate_request makes truncation unreachable from outside,
        so force it white-box: once the request is in a slot (first
        token arrived), grow max_new_tokens so the slot runs out of
        cache positions mid-request, and check the finish event flags
        the short stream."""

        async def go():
            eng = make_engine(params, batch_slots=1, max_len=8)
            async with AsyncEngine(eng) as fe:
                stream = await fe.submit(make_prompts()[1], 3)
                await stream.__anext__()  # admitted: validation is behind us
                stream.request.max_new_tokens = 10  # 5 + 10 > max_len now
                await stream.collect()
            return stream

        stream = asyncio.run(go())
        assert stream.truncated
        assert len(stream.tokens) == 4  # (max_len 8) - (prompt 5) + 1
        assert stream.events[-1] == dataclasses.replace(
            stream.events[-1], kind="finished", detail="truncated")

    def test_driver_crash_closes_streams(self, params):
        """An unexpected engine error must end every stream (detail
        'driver_error') instead of hanging clients, and stop() must
        re-raise the original exception."""

        async def go():
            eng = make_engine(params)
            fe = AsyncEngine(eng)
            await fe.start()
            stream = await fe.submit(make_prompts()[0], 4)

            def boom():
                raise RuntimeError("boom")

            eng.step = boom
            await stream.collect()  # must terminate, not hang
            assert stream.status == "dropped"
            assert stream.events[-1].detail == "driver_error"
            assert fe.in_flight == 0
            with pytest.raises(RuntimeError, match="boom"):
                await fe.stop()

        asyncio.run(go())

    def test_counters_and_summary(self, params):
        async def go():
            eng = make_engine(params)
            async with AsyncEngine(eng) as fe:
                streams = [await fe.submit(p, 3) for p in make_prompts()[:3]]
                await asyncio.gather(*(s.collect() for s in streams))
                return fe.summary()

        summ = asyncio.run(go())
        assert summ["frontend_submitted"] == 3.0
        assert summ["frontend_finished"] == 3.0
        assert summ["frontend_dropped"] == summ["frontend_cancelled"] == 0.0
        assert summ["frontend_waiting"] == summ["frontend_live"] == 0.0
        assert summ["generated_tokens"] == 9.0


# ---------------------------------------------------------------------------
# Backpressure, timeouts, deadlines, cancellation
# ---------------------------------------------------------------------------


class TestBackpressure:
    def test_waiting_room_overflow_raises(self, params):
        """Engine queue full -> waiting room fills -> AdmissionError to
        the caller.  Exact because back-to-back submits never yield to
        the driver task."""

        async def go():
            eng = make_engine(params, batch_slots=1, max_queue=1)
            async with AsyncEngine(eng, waiting_room=2) as fe:
                streams = [await fe.submit(make_prompts()[0], 2)
                           for _ in range(2)]
                with pytest.raises(AdmissionError, match="waiting room"):
                    for _ in range(8):
                        streams.append(await fe.submit(make_prompts()[0], 2))
                await asyncio.gather(*(s.collect() for s in streams))
                assert all(s.status == "finished" for s in streams)
                # room drained: submits are accepted again
                late = await fe.submit(make_prompts()[0], 2)
                await late.collect()
                assert late.status == "finished"

        asyncio.run(go())

    def test_invalid_requests_rejected_eagerly(self, params):
        """validate_request runs at submit: requests the engine can
        never serve fail in the caller, not in the driver loop."""

        async def go():
            eng = make_engine(params, batch_slots=1)
            async with AsyncEngine(eng) as fe:
                with pytest.raises(InvalidRequestError):
                    await fe.submit([], 4)  # empty prompt
                with pytest.raises(InvalidRequestError):
                    await fe.submit([1, 2, 3], 0)  # no tokens requested
                with pytest.raises(InvalidRequestError):
                    await fe.submit(list(range(64)), 4)  # > max_len
                ok = await fe.submit([1, 2, 3], 2)
                await ok.collect()
                with pytest.raises(ValueError, match="already in flight"):
                    stream = await fe.submit([1, 2, 3], 8, uid=7)
                    await fe.submit([4, 5], 2, uid=7)
                await stream.collect()

        asyncio.run(go())

    def test_queue_timeout_zero_sheds_unadmittable_load(self, params):
        """queue_timeout=0 is 'admit now or drop': with the slot and the
        engine queue both occupied, a third request is dropped at the
        driver's next turn, with the drop visible in events/counters."""

        async def go():
            eng = make_engine(params, batch_slots=1, max_queue=1)
            async with AsyncEngine(eng, queue_timeout=0.0) as fe:
                a = await fe.submit(make_prompts()[2], 8)
                b = await fe.submit(make_prompts()[0], 2)
                c = await fe.submit(make_prompts()[1], 2)
                await asyncio.gather(a.collect(), b.collect(), c.collect())
                return fe, a, b, c

        fe, a, b, c = asyncio.run(go())
        # only a fit the engine queue at the driver's first turn; b and c
        # were not admittable *right then*, so zero-timeout sheds both
        assert a.status == "finished"
        for s in (b, c):
            assert s.status == "dropped"
            assert s.events[-1].kind == "dropped"
            assert s.events[-1].detail == "queue_timeout"
            assert s.tokens == []
        assert fe.counters["dropped"] == 2

    @pytest.mark.parametrize("cache", ["dense", "paged"])
    def test_deadline_drop_reclaims_resources(self, params, cache):
        """A request whose TTFT deadline passes before its first token is
        dropped and cancelled inside the engine — slot and pages come
        back, and the engine keeps serving everyone else."""
        kw = dict(cache=cache)
        if cache == "paged":
            kw["page_size"] = 8

        async def go():
            eng = make_engine(params, batch_slots=1, **kw)
            async with AsyncEngine(eng) as fe:
                doomed = await fe.submit(make_prompts()[2], 8, deadline_s=0.0)
                live = await fe.submit(make_prompts()[0], 4)
                await asyncio.gather(doomed.collect(), live.collect())
                return eng, fe, doomed, live

        eng, fe, doomed, live = asyncio.run(go())
        assert doomed.status == "dropped"
        assert doomed.events[-1].detail == "deadline"
        assert not doomed.met_deadline
        assert doomed.request.cancelled and doomed.request.output == []
        assert live.status == "finished" and len(live.tokens) == 4
        assert live.met_deadline  # vacuous: no deadline set, token arrived
        assert eng.stats_summary()["cancelled"] == 1.0
        if eng.kv is not None:
            assert eng.kv.tables.used_pages == 0
            eng.kv.check_invariants()

    def test_stream_cancel_mid_flight(self, params):
        """stream.cancel() after tokens have streamed: the stream ends
        with status 'cancelled', the engine reclaims the slot, and a
        queued request takes it over."""

        async def go():
            eng = make_engine(params, batch_slots=1)
            async with AsyncEngine(eng) as fe:
                victim = await fe.submit(make_prompts()[0], 16)
                successor = await fe.submit(make_prompts()[1], 3)
                got = []
                async for tok in victim:
                    got.append(tok)
                    if len(got) == 2:
                        victim.cancel()
                        victim.cancel()  # idempotent
                await successor.collect()
                return fe, victim, successor, got

        fe, victim, successor, got = asyncio.run(go())
        assert victim.status == "cancelled"
        assert 2 <= len(victim.tokens) < 16  # ended early, stream closed
        assert successor.status == "finished" and len(successor.tokens) == 3
        assert fe.counters["cancelled"] == 1
        assert fe.engine.stats_summary()["cancelled"] == 1.0

    def test_stop_without_drain_sheds_in_flight(self, params):
        async def go():
            eng = make_engine(params, batch_slots=1)
            fe = AsyncEngine(eng)
            await fe.start()
            stream = await fe.submit(make_prompts()[0], 21)
            await asyncio.sleep(0.001)  # let it get under way
            await fe.stop(drain=False)
            return fe, stream

        fe, stream = asyncio.run(go())
        assert stream.status == "dropped"
        assert stream.events[-1].detail == "shutdown"
        assert fe.in_flight == 0


# ---------------------------------------------------------------------------
# met_deadline: never a TypeError, False without a first token
# ---------------------------------------------------------------------------


class TestMetDeadline:
    """``met_deadline`` compares ``ttft <= deadline_s`` — both can be
    None.  The contract: a request that never produced a first token
    (dropped, cancelled, or still queued) is ``False``, never a
    ``TypeError``, with or without a deadline set."""

    def test_cancelled_before_first_token(self, params):
        async def go():
            eng = make_engine(params, batch_slots=1)
            async with AsyncEngine(eng) as fe:
                blocker = await fe.submit(make_prompts()[2], 8)
                # queued behind the blocker: cancelled with no tokens,
                # one with a deadline and one without
                v1 = await fe.submit(make_prompts()[0], 4, deadline_s=60.0)
                v2 = await fe.submit(make_prompts()[1], 4)
                v1.cancel()
                v2.cancel()
                await asyncio.gather(
                    blocker.collect(), v1.collect(), v2.collect()
                )
                return blocker, v1, v2

        blocker, v1, v2 = asyncio.run(go())
        assert blocker.status == "finished" and blocker.met_deadline
        for v in (v1, v2):
            assert v.status == "cancelled" and v.ttft is None
            assert v.met_deadline is False  # no first token -> False

    def test_queue_timeout_drop_without_deadline(self, params):
        """The shape the old expression would have TypeError'd on:
        dropped before any token, ``deadline_s=None`` — the
        ``self.deadline_s is None`` arm short-circuits True while
        ``ttft`` is still None."""

        async def go():
            eng = make_engine(params, batch_slots=1, max_queue=1)
            async with AsyncEngine(eng, queue_timeout=0.0) as fe:
                a = await fe.submit(make_prompts()[2], 6)
                b = await fe.submit(make_prompts()[0], 2)  # shed, no deadline
                await asyncio.gather(a.collect(), b.collect())
                return a, b

        a, b = asyncio.run(go())
        assert b.status == "dropped" and b.ttft is None
        assert b.met_deadline is False
        assert a.met_deadline is True

    def test_before_first_token_is_false_not_error(self, params):
        async def go():
            eng = make_engine(params)
            async with AsyncEngine(eng) as fe:
                s = await fe.submit(make_prompts()[0], 2)
                early = s.met_deadline  # queued: ttft is None
                await s.collect()
                return early, s

        early, s = asyncio.run(go())
        assert early is False
        assert s.met_deadline is True


# ---------------------------------------------------------------------------
# sampling passes through the front-end
# ---------------------------------------------------------------------------


class TestFrontendSampling:
    def test_sampled_streams_match_sync_driver(self, params):
        """submit(sampling=...) threads SamplingParams to the engine:
        async streams == the synchronous driver's seeded streams."""
        from repro.serve import SamplingParams

        prompts = make_prompts()
        sp = SamplingParams(temperature=0.8, top_p=0.95)
        eng = make_engine(params)
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=list(p), max_new_tokens=4,
                               sampling=sp.with_seed(i)))
        eng.run()
        want = {u: r.output for u, r in eng.finished.items()}

        async def go():
            eng2 = make_engine(params)
            async with AsyncEngine(eng2) as fe:
                streams = [
                    await fe.submit(p, 4, sampling=sp.with_seed(i))
                    for i, p in enumerate(prompts)
                ]
                outs = await asyncio.gather(*(s.collect() for s in streams))
            return {s.uid: out for s, out in zip(streams, outs)}

        assert asyncio.run(go()) == want


# ---------------------------------------------------------------------------
# Step callbacks and the step log
# ---------------------------------------------------------------------------


class TestStepCallbacks:
    def test_callback_per_step_sync_driver(self, params):
        eng = make_engine(params)
        seen = []
        eng.add_step_callback(seen.append)
        for i, p in enumerate(make_prompts()[:3]):
            eng.submit(Request(uid=i, prompt=list(p), max_new_tokens=3))
        eng.run()
        assert len(seen) == eng.steps
        assert all(isinstance(s, StepStats) for s in seen)
        assert [s.step for s in seen] == list(range(eng.steps))
        assert seen is not eng.step_stats and seen == eng.step_stats

    def test_frontend_step_log_mirrors_engine(self, params):
        """Driven through the front-end, the engine's own step log
        (``step_stats``, the one per-step record) holds every step with
        its phases and why admission stopped."""
        async def go():
            eng = make_engine(params)  # 2 slots, 4 requests at once
            async with AsyncEngine(eng) as fe:
                streams = [await fe.submit(p, 4) for p in make_prompts()[:4]]
                await asyncio.gather(*(s.collect() for s in streams))
                return fe

        fe = asyncio.run(go())
        log = fe.engine.step_stats
        assert len(log) == fe.engine.steps > 0
        assert [s.step for s in log] == list(range(len(log)))
        for s in log:
            assert {"admit", "schedule", "dispatch", "sync", "emit"} <= set(s.phases)
            assert 0 < sum(s.phases.values()) <= s.wall_time
            # queue depth at step start is recorded for queue-pressure stats
            assert s.queued_requests >= 0
            assert s.admit_blocked in (None, "slots")
            assert (s.admit_blocked is None) or s.queued_requests > 0
        assert "slots" in [s.admit_blocked for s in log]
        assert log[-1].admit_blocked is None


# ---------------------------------------------------------------------------
# TTFT accounting split (satellite: queue_wait + admitted_ttft == ttft)
# ---------------------------------------------------------------------------


class TestTTFTAccounting:
    def test_hand_computed_split(self, params):
        """Regression-pin the stats_summary percentiles against requests
        with hand-crafted timestamps: queue_wait = admitted - submitted,
        admitted_ttft = first_token - admitted, ttft = their sum."""
        eng = make_engine(params)
        stamps = [  # (submitted, admitted, first_token)
            (10.0, 10.5, 11.0),   # qw 0.5,  attft 0.5,  ttft 1.0
            (20.0, 20.25, 21.25),  # qw 0.25, attft 1.0,  ttft 1.25
            (30.0, 32.0, 32.5),   # qw 2.0,  attft 0.5,  ttft 2.5
        ]
        for i, (sub, adm, ftk) in enumerate(stamps):
            r = Request(uid=i, prompt=[1, 2], max_new_tokens=1, output=[5],
                        submitted_at=sub, admitted_at=adm, first_token_at=ftk,
                        finished_at=ftk)
            assert r.ttft == pytest.approx(r.queue_wait + r.admitted_ttft)
            eng.finished[i] = r
        s = eng.stats_summary()
        qw, at = [0.5, 0.25, 2.0], [0.5, 1.0, 0.5]
        assert s["mean_queue_wait"] == pytest.approx(np.mean(qw))
        assert s["p50_queue_wait"] == pytest.approx(np.quantile(qw, 0.5))
        assert s["p99_queue_wait"] == pytest.approx(np.quantile(qw, 0.99))
        assert s["mean_admitted_ttft"] == pytest.approx(np.mean(at))
        assert s["p50_admitted_ttft"] == pytest.approx(np.quantile(at, 0.5))
        assert s["p99_admitted_ttft"] == pytest.approx(np.quantile(at, 0.99))
        assert s["mean_ttft"] == pytest.approx(
            s["mean_queue_wait"] + s["mean_admitted_ttft"])
        assert s["p50_ttft"] == pytest.approx(np.quantile([1.0, 1.25, 2.5], .5))

    def test_ttft_measured_from_frontend_submit(self, params):
        """A request held in the front-end waiting room accrues TTFT from
        submit(): queue_wait covers the waiting room + engine queue, and
        the identity ttft = queue_wait + admitted_ttft holds on real
        (wall-clock) runs too."""

        async def go():
            eng = make_engine(params, batch_slots=1, max_queue=1)
            async with AsyncEngine(eng, waiting_room=8) as fe:
                streams = [await fe.submit(make_prompts()[0], 4)
                           for _ in range(4)]
                await asyncio.gather(*(s.collect() for s in streams))
            return streams

        streams = asyncio.run(go())
        for s in streams:
            r = s.request
            assert r.ttft == pytest.approx(r.queue_wait + r.admitted_ttft)
        # the last request waited for three predecessors through one slot:
        # queue wait must dominate its TTFT, not be hidden by re-stamping
        last = streams[-1].request
        assert last.queue_wait > streams[0].request.queue_wait
        assert last.queue_wait >= last.admitted_ttft
