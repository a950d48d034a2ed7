"""repro.train.resilience: telemetry, fault injection, the online tau
controller, and their wiring through the trainer (parity, adaptation,
checkpoint restore-parity)."""
import numpy as np
import pytest

from repro.core import DropConfig
from repro.core.simulate import LatencyModel, NoiseModel
from repro.core.threshold import fill_profile_nans, select_threshold
from repro.data import DataConfig
from repro.models import ModelConfig
from repro.train import TrainConfig, train
from repro.train.resilience import (
    BadNode,
    ComputeTelemetry,
    ControllerConfig,
    FaultyLatencyModel,
    P2Quantile,
    ParetoTail,
    RingBuffer,
    StreamingMoments,
    TauController,
    effective_speedup_at,
    make_scenario,
)

MILD = LatencyModel(base=0.45, noise=NoiseModel(kind="normal", mean=0.1, var=0.002))
TINY = ModelConfig(
    name="tiny", n_layers=1, d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
    vocab_size=131, dtype="float32", remat=False,
)
DATA = DataConfig(vocab_size=131, seq_len=32, batch_size=32, strategy="pack", seed=0)


def _feed(ctl, tel, latency, steps, n=8, m=8, seed=1):
    for s in range(steps):
        tel.record(s, latency.sample_at(s, n, m, seed=seed), tau=ctl.tau)
        ctl.maybe_update(s, tel, steps_remaining=steps - s)


class TestTelemetry:
    def test_ring_buffer_bound_and_order(self):
        rb = RingBuffer(4)
        for i in range(11):
            rb.push(float(i))
            assert len(rb) <= 4
        assert rb.window().tolist() == [7.0, 8.0, 9.0, 10.0]
        assert rb.total_pushed == 11

    def test_ring_buffer_shape_check(self):
        rb = RingBuffer(2, (3,))
        with pytest.raises(ValueError):
            rb.push(np.zeros(4))

    def test_streaming_moments_match_numpy(self):
        rng = np.random.default_rng(0)
        x = rng.lognormal(0.0, 1.0, size=500)
        sm = StreamingMoments()
        for chunk in np.split(x, 10):
            sm.push(chunk)
        assert sm.mean == pytest.approx(float(x.mean()), rel=1e-9)
        assert sm.std == pytest.approx(float(x.std()), rel=1e-9)

    def test_p2_quantile_approximates(self):
        rng = np.random.default_rng(1)
        x = rng.normal(10.0, 2.0, size=5000)
        p2 = P2Quantile(0.9)
        p2.push(x)
        assert p2.value == pytest.approx(float(np.quantile(x, 0.9)), rel=0.05)

    def test_record_validates_shape_and_summary(self):
        tel = ComputeTelemetry(2, 3, window=8)
        with pytest.raises(ValueError):
            tel.record(0, np.zeros((3, 2)))
        for s in range(12):
            tel.record(s, np.full((2, 3), 0.5))
        assert tel.steps == 12 and tel.window_size == 8
        summ = tel.summary()
        assert summ["mb_mean_s"] == pytest.approx(0.5)
        assert summ["worker_step_mean_s"] == pytest.approx(1.5)

    def test_state_roundtrip_preserves_window(self):
        tel = ComputeTelemetry(2, 2, window=4)
        rng = np.random.default_rng(2)
        for s in range(9):
            tel.record(s, rng.random((2, 2)))
        fresh = ComputeTelemetry(2, 2, window=4)
        fresh.load_state_dict(tel.state_dict())
        assert fresh.steps == tel.steps
        np.testing.assert_allclose(fresh.window(), tel.window())
        assert fresh.summary()["mb_mean_s"] == pytest.approx(tel.summary()["mb_mean_s"])

    def test_ingest_host_profile_fills_nans(self):
        prof = np.full((3, 1, 4), 0.5)
        prof[1, 0, 3] = np.nan  # a dropped micro-batch in the host log
        tel = ComputeTelemetry(2, 4, window=8)
        tel.ingest_host_profile(prof)
        assert tel.steps == 3
        assert np.isfinite(tel.window()).all()


class TestFaults:
    def test_deterministic_and_call_order_independent(self):
        a = make_scenario("pareto", seed=7)
        b = make_scenario("pareto", seed=7)
        t5 = a.sample_at(5, 4, 4)
        np.testing.assert_array_equal(t5, b.sample_at(5, 4, 4))
        b.sample_at(99, 4, 4)  # draws elsewhere must not shift step 5
        np.testing.assert_array_equal(t5, b.sample_at(5, 4, 4))

    def test_seed_changes_stream(self):
        a = make_scenario("pareto", seed=0)
        assert not np.array_equal(a.sample_at(3, 4, 4), a.sample_at(3, 4, 4, seed=1))

    def test_badnode_hits_only_its_rank_after_start(self):
        lat = FaultyLatencyModel(base=MILD, faults=(BadNode(rank=1, factor=3.0, start=10),))
        base = MILD.sample_at(5, 4, 4, seed=0)
        np.testing.assert_array_equal(lat.sample_at(5, 4, 4, seed=0), base)
        after = lat.sample_at(12, 4, 4, seed=0)
        base12 = MILD.sample_at(12, 4, 4, seed=0)
        np.testing.assert_allclose(after[1], base12[1] * 3.0)
        np.testing.assert_array_equal(np.delete(after, 1, 0), np.delete(base12, 1, 0))

    def test_host_delay_matches_perturbation(self):
        lat = make_scenario("badnode", seed=0, onset=0)
        for rank in range(4):
            d = lat.host_delay_at(3, rank, 4, 4)
            assert d >= 0.0
        # the bad rank's delay is the dominant one
        delays = [lat.host_delay_at(3, r, 8, 4) for r in range(8)]
        assert int(np.argmax(delays)) == 2  # SCENARIOS pins rank=2

    def test_onset_override_and_unknown_scenario(self):
        lat = make_scenario("badnode", seed=0, onset=50)
        assert lat.faults[0].start == 50
        with pytest.raises(ValueError):
            make_scenario("nope")


class TestController:
    def test_noop_on_mild_cluster(self):
        """No tail => S_eff ~ 1 everywhere => tau stays inf (the parity
        contract the trainer test pins end-to-end)."""
        tel = ComputeTelemetry(8, 8, window=32)
        ctl = TauController(ControllerConfig(warmup_steps=8, check_every=4), tc=0.5)
        _feed(ctl, tel, MILD, 60)
        assert not np.isfinite(ctl.tau)
        assert ctl.rebuilds == 0
        assert all(not d.applied for d in ctl.decisions)

    def test_applies_under_heavy_tail(self):
        tel = ComputeTelemetry(8, 8, window=32)
        ctl = TauController(ControllerConfig(warmup_steps=8, check_every=4), tc=0.5)
        _feed(ctl, tel, make_scenario("pareto", seed=0), 40)
        assert np.isfinite(ctl.tau)
        assert ctl.rebuilds >= 1
        assert ctl.trajectory[0] == (0, float("inf"))

    def test_gate_blocks_unamortizable_rebuild(self):
        """With a recompile cost no per-step gain can repay, tau never
        moves — however heavy the tail."""
        tel = ComputeTelemetry(8, 8, window=32)
        ctl = TauController(
            ControllerConfig(warmup_steps=8, check_every=4, recompile_cost_s=1e9),
            tc=0.5,
        )
        _feed(ctl, tel, make_scenario("pareto", seed=0), 60)
        assert not np.isfinite(ctl.tau)
        assert any(d.reason == "not_amortized" for d in ctl.decisions)

    def test_max_drop_guardrail(self):
        """The applied tau's completion respects 1 - max_drop."""
        tel = ComputeTelemetry(8, 8, window=32)
        cfg = ControllerConfig(warmup_steps=8, check_every=4, max_drop=0.25)
        ctl = TauController(cfg, tc=0.5)
        _feed(ctl, tel, make_scenario("pareto", seed=0), 40)
        assert np.isfinite(ctl.tau)
        _, completion = effective_speedup_at(tel.window(), 0.5, ctl.tau)
        assert completion >= 1.0 - cfg.max_drop - 0.05  # window drifts a little

    def test_state_roundtrip(self):
        tel = ComputeTelemetry(8, 8, window=32)
        ctl = TauController(ControllerConfig(warmup_steps=8, check_every=4), tc=0.5)
        _feed(ctl, tel, make_scenario("pareto", seed=0), 40)
        fresh = TauController(ctl.cfg, tc=0.5)
        fresh.load_state_dict(ctl.state_dict())
        assert fresh.tau == ctl.tau
        assert fresh.trajectory == ctl.trajectory
        assert fresh._last_check == ctl._last_check


class TestThresholdGuards:
    def test_fill_profile_nans(self):
        prof = np.full((4, 2, 3), 1.0)
        prof[2, 1, 2] = np.nan
        filled = fill_profile_nans(prof)
        assert np.isfinite(filled).all()
        assert filled[2, 1, 2] == pytest.approx(1.0)

    def test_select_threshold_max_drop(self):
        rng = np.random.default_rng(0)
        prof = rng.lognormal(0.0, 1.0, size=(40, 8, 8))
        res = select_threshold(prof, tc=0.5, max_drop=0.2)
        cum = np.cumsum(prof, axis=-1)
        done = (cum < res.tau) | (np.arange(8) < 1)
        assert done.mean() >= 0.8 - 1e-9


class TestTrainerResilience:
    def _cfg(self, **kw):
        base = dict(
            steps=30, n_workers=4, microbatches=4, lr=1e-3, seed=0,
            tc=0.5, telemetry_window=16, log_every=0,
        )
        base.update(kw)
        return TrainConfig(**base)

    def test_no_fault_parity_controller_is_noop(self):
        """Controller on + no faults == the no-drop baseline, bit for bit."""
        lat = make_scenario("none", seed=0)
        off = train(TINY, DATA, self._cfg(latency=lat, drop=DropConfig(enabled=False)))
        on = train(TINY, DATA, self._cfg(
            latency=lat, drop=DropConfig(enabled=True, tau=float("inf")),
            online_tau=True,
            controller=ControllerConfig(warmup_steps=8, check_every=4),
        ))
        assert on.losses == off.losses
        assert on.metrics["tau_changes"] == 0
        assert not np.isfinite(on.tau)
        assert float(np.mean(on.drop_fractions)) == 0.0

    def test_midrun_slow_rank_online_adapts_and_cuts_iter_time(self):
        """A rank going 4x slower mid-run: the online controller applies a
        finite tau and post-onset iteration time drops measurably below
        the unadapted (tau = inf) run on the identical latency stream."""
        onset = 10
        lat = FaultyLatencyModel(
            base=MILD, faults=(BadNode(rank=1, factor=4.0, start=onset),)
        )
        kw = dict(latency=lat, steps=40)
        off = train(TINY, DATA, self._cfg(drop=DropConfig(enabled=False), **kw))
        on = train(TINY, DATA, self._cfg(
            drop=DropConfig(enabled=True, tau=float("inf")), online_tau=True,
            controller=ControllerConfig(warmup_steps=8, check_every=4), **kw,
        ))
        assert on.metrics["tau_changes"] >= 1
        applied_at = on.tau_trajectory[1][0]
        post_on = float(np.mean(on.sim_times[applied_at:]))
        post_off = float(np.mean(off.sim_times[applied_at:]))
        assert post_on < 0.8 * post_off, (post_on, post_off)
        # and the drop stays bounded: only the slow rank's tail is cut
        assert float(np.mean(on.drop_fractions)) < 0.3

    def test_pareto_ramp_online_beats_stale_static(self):
        """The acceptance shape: under a heavy tail plus a mid-run base
        ramp the one-shot calibration goes stale; online goodput must be
        strictly higher (BENCH_train.json commits the full record)."""
        lat = make_scenario("pareto", seed=0, onset=25)
        kw = dict(latency=lat, steps=60)
        static = train(TINY, DATA, self._cfg(
            drop=DropConfig(enabled=True, tau=float("inf")),
            auto_threshold=True, calibration_steps=12, **kw,
        ))
        online = train(TINY, DATA, self._cfg(
            drop=DropConfig(enabled=True, tau=float("inf")), online_tau=True,
            controller=ControllerConfig(warmup_steps=8, check_every=4), **kw,
        ))

        def goodput(r):
            good = np.sum(1.0 - np.asarray(r.drop_fractions))
            return float(good / np.sum(r.sim_times))

        assert online.metrics["tau_changes"] >= 1
        assert goodput(online) > goodput(static), (
            goodput(online), goodput(static),
        )

    def test_checkpoint_restore_parity(self, tmp_path):
        """Interrupting at the midpoint and resuming reproduces the
        uninterrupted run exactly: losses, tau trajectory, drop rates —
        the adapted tau and the telemetry window ride the checkpoint."""
        ckpt = str(tmp_path / "ckpt")
        lat = make_scenario("pareto", seed=0, onset=10)
        kw = dict(
            latency=lat, steps=40,
            drop=DropConfig(enabled=True, tau=float("inf")), online_tau=True,
            controller=ControllerConfig(warmup_steps=8, check_every=4),
        )
        part = train(TINY, DATA, self._cfg(
            steps=20, ckpt_dir=ckpt, ckpt_every=20, **{k: v for k, v in kw.items() if k != "steps"},
        ))
        resumed = train(TINY, DATA, self._cfg(resume_from=ckpt, **kw))
        full = train(TINY, DATA, self._cfg(**kw))

        assert part.losses == full.losses[:20]
        assert resumed.losses == full.losses[20:]
        assert resumed.drop_fractions == full.drop_fractions[20:]
        assert resumed.tau == pytest.approx(full.tau)
        assert resumed.tau_trajectory == full.tau_trajectory

    def test_result_exposes_drop_and_tau_series(self):
        lat = make_scenario("pareto", seed=0, onset=10)
        r = train(TINY, DATA, self._cfg(
            latency=lat, steps=30,
            drop=DropConfig(enabled=True, tau=float("inf")), online_tau=True,
            controller=ControllerConfig(warmup_steps=8, check_every=4),
        ))
        assert r.drop_rates == r.drop_fractions
        taus = r.tau_series()
        assert taus.shape == (30,)
        assert not np.isfinite(taus[0])
        if r.metrics["tau_changes"]:
            step0 = r.tau_trajectory[1][0]
            assert np.isfinite(taus[step0:]).all()
        assert r.telemetry is not None and r.telemetry["steps"] == 30
        assert len(r.host_step_s) == 30 and min(r.host_step_s) > 0
