"""Training loop with DropCompute as a first-class feature.

The trainer virtualizes N data-parallel workers on whatever devices exist:
each step draws a (N, M) micro-batch latency tensor from a ``LatencyModel``
(or a ``resilience.faults`` scenario wrapping one), derives the Algorithm-1
drop mask, and accumulates masked gradients.  Simulated iteration time

    T_iter = max_n min(T_n, tau) + T_c

is tracked per step so loss-vs-wallclock curves (paper fig. 5) come out of
any run.

Threshold selection runs in one of two modes:

* **static** (``auto_threshold=True``): the original one-shot Algorithm 2
  after ``calibration_steps`` profiling steps;
* **online** (``online_tau=True``): a ``resilience.TauController``
  re-estimates tau* from the rolling telemetry window during the run —
  with hysteresis, drop guardrails and a recompile-amortization gate,
  since on the SPMD path tau is baked into the traced drop mask and every
  change costs a ``build_bundle`` rebuild.

Per-step compute telemetry (simulated draws reconciled with the monotonic
host clock around the jitted step) is always collected; the controller
state and telemetry summary ride checkpoints, so a restarted run resumes
with its adapted tau instead of re-calibrating from scratch.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dropcompute import DropConfig, accumulate_grads, drop_mask
from ..core.engine import make_grad_fn
from ..core.simulate import LatencyModel
from ..core.threshold import select_threshold
from ..data.synthetic import DataConfig, batch_at, microbatches_at
from ..dist import Distribution
from ..models import InputShape, ModelConfig, init_params, loss_fn
from ..optim import apply_updates, clip_by_global_norm, make as make_opt
from . import checkpoint as ckpt
from .resilience import ComputeTelemetry, ControllerConfig, TauController

PyTree = Any


@dataclasses.dataclass
class TrainConfig:
    steps: int = 100
    n_workers: int = 8  # virtual data-parallel workers
    microbatches: int = 4  # M (gradient accumulations per worker)
    optimizer: str = "adamw"
    lr: float = 3e-4
    weight_decay: float = 0.01
    clip_norm: float = 1.0
    seed: int = 0
    # DropCompute
    drop: DropConfig = dataclasses.field(default_factory=lambda: DropConfig(enabled=False))
    latency: LatencyModel = dataclasses.field(default_factory=LatencyModel)
    tc: float = 0.5  # serial/communication seconds per iteration
    calibration_steps: int = 20  # Algorithm 2 profiling window
    auto_threshold: bool = False  # static: one-shot tau* after calibration
    # Online tau (repro.train.resilience): re-estimate tau* from rolling
    # telemetry during the run; ``controller`` overrides the default knobs.
    online_tau: bool = False
    controller: Optional[ControllerConfig] = None
    telemetry_window: int = 64
    # Fault scenarios already live in ``latency`` (a FaultyLatencyModel);
    # set this to additionally *sleep* the injected delays around the real
    # step (physical compute variance on SPMD runs).
    inject_real_delays: bool = False
    # Distribution: None = single-device virtual-worker loop; a mesh spec
    # ("4,2", a dim tuple, or a repro.dist.Distribution) switches to the
    # sharded SPMD step built by ``Distribution.train_step``.
    mesh: Optional[Any] = None
    # bookkeeping
    log_every: int = 10
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 0
    resume_from: Optional[str] = None  # checkpoint dir to resume from


@dataclasses.dataclass
class TrainResult:
    params: PyTree
    losses: List[float]
    sim_times: List[float]  # simulated seconds per iteration
    drop_fractions: List[float]  # per-step drop rate (1 - completed fraction)
    tau: float  # final threshold (back-compat scalar)
    metrics: Dict[str, Any]
    # (step, tau) at every threshold change, starting with the initial tau;
    # the full trajectory, not just the final scalar.
    tau_trajectory: List[Tuple[int, float]] = dataclasses.field(default_factory=list)
    telemetry: Optional[Dict[str, Any]] = None  # ComputeTelemetry.summary()
    # host-clock seconds around each jitted step, up to block_until_ready
    # (the first includes its compilation)
    host_step_s: List[float] = dataclasses.field(default_factory=list)

    @property
    def cum_time(self) -> np.ndarray:
        return np.cumsum(self.sim_times)

    @property
    def drop_rates(self) -> List[float]:
        """Per-step drop rate; alias of ``drop_fractions`` under the name
        the benchmark/figure scripts use."""
        return self.drop_fractions

    def tau_series(self, start_step: int = 0) -> np.ndarray:
        """Per-step tau in effect, aligned with ``losses`` (len(losses),)."""
        n = len(self.losses)
        out = np.full(n, np.inf)
        traj = self.tau_trajectory or [(start_step, self.tau)]
        for step, tau in traj:
            i = max(int(step) - start_step, 0)
            if i < n:
                out[i:] = tau
        return out


def _resolve_dist(mesh) -> Optional[Distribution]:
    """None | "4,2" | (4, 2) | Mesh | Distribution -> Optional[Distribution]."""
    if mesh is None:
        return None
    if isinstance(mesh, Distribution):
        return mesh
    if isinstance(mesh, jax.sharding.Mesh):
        return Distribution(mesh)
    return Distribution.from_spec(mesh)


def _make_step(model_cfg: ModelConfig, tcfg: TrainConfig, lr_fn):
    opt = make_opt(
        tcfg.optimizer, lr_fn, weight_decay=tcfg.weight_decay
    ) if tcfg.optimizer != "sgd" else make_opt(tcfg.optimizer, lr_fn)
    grad_fn = make_grad_fn(lambda p, mb: loss_fn(p, model_cfg, mb))

    def step(params, opt_state, microbatch_stack, mask):
        grads, loss, stats = accumulate_grads(
            grad_fn, params, microbatch_stack, mask, tcfg.drop
        )
        if tcfg.clip_norm > 0:
            grads = clip_by_global_norm(grads, tcfg.clip_norm)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, loss, stats

    return opt, jax.jit(step)


def _latencies_at(tcfg: TrainConfig, step: int, n: int, m: int) -> np.ndarray:
    """The step's (N, M) latency draw, keyed by (seed, step) so resumed
    runs replay the identical stream (``sample_at`` seam on both
    ``LatencyModel`` and ``resilience.FaultyLatencyModel``)."""
    return np.asarray(tcfg.latency.sample_at(step, n, m, seed=tcfg.seed + 1))


def train(
    model_cfg: ModelConfig,
    data_cfg: DataConfig,
    tcfg: TrainConfig,
    params: Optional[PyTree] = None,
    eval_fn: Optional[Callable[[PyTree], float]] = None,
) -> TrainResult:
    n, m = tcfg.n_workers, tcfg.microbatches
    total_m = n * m
    assert data_cfg.batch_size % total_m == 0, (
        f"global batch {data_cfg.batch_size} must divide into {n} workers x {m} microbatches"
    )

    if params is None:
        params = init_params(jax.random.PRNGKey(tcfg.seed), model_cfg)

    # --- distribution: resolve the SPMD path up front --------------------
    dist = _resolve_dist(tcfg.mesh)
    bundle = None
    build_s = 0.0  # measured bundle-build cost (the recompile the gate amortizes)
    if dist is not None:
        shape = InputShape(
            "train_cli", data_cfg.seq_len, data_cfg.batch_size, "train",
            microbatches=m,
        )

        def build_bundle(tau_now: float):
            drop = dataclasses.replace(tcfg.drop, tau=tau_now)
            # sgd: no decay, mirroring _make_step's single-device path —
            # the same TrainConfig must train identically on both paths
            wd = None if tcfg.optimizer == "sgd" else tcfg.weight_decay
            return dist.train_step(
                model_cfg, shape, drop, n_workers=n,
                optimizer=tcfg.optimizer, lr=tcfg.lr,
                clip_norm=tcfg.clip_norm, weight_decay=wd,
            )

        b0 = time.monotonic()
        bundle = build_bundle(tcfg.drop.tau)
        build_s = time.monotonic() - b0
        opt = bundle.opt
        params = dist.shard(params)
        opt_state = opt.init(params)
    else:
        opt, step_fn = _make_step(model_cfg, tcfg, lambda s: tcfg.lr)
        opt_state = opt.init(params)

    tau = tcfg.drop.tau
    profile: List[np.ndarray] = []

    # --- resilience: telemetry always on, controller when online_tau -----
    telemetry = ComputeTelemetry(n, m, window=tcfg.telemetry_window)
    controller: Optional[TauController] = None
    if tcfg.online_tau and tcfg.drop.enabled:
        ccfg = tcfg.controller or ControllerConfig(
            min_microbatches=tcfg.drop.min_microbatches
        )
        controller = TauController(
            ccfg, tcfg.tc, tau=tau, total_steps=tcfg.steps,
            default_recompile_cost_s=build_s if bundle is not None else 0.0,
        )

    # --- resume: params/opt/step plus the adapted tau + controller state --
    start_step = 0
    if tcfg.resume_from:
        restored, start_step = ckpt.restore(
            tcfg.resume_from, {"params": params, "opt": opt_state}
        )
        params, opt_state = restored["params"], restored["opt"]
        if dist is not None:
            params = dist.shard(params)
        state = ckpt.resilience_state(tcfg.resume_from)
        if state:
            tau = float("inf") if state.get("tau") is None else float(state["tau"])
            if controller is not None and state.get("controller"):
                controller.load_state_dict(state["controller"])
                tau = controller.tau
            if state.get("telemetry"):
                telemetry.load_state_dict(state["telemetry"])
            if bundle is not None and tau != tcfg.drop.tau:
                bundle = build_bundle(tau)

    trajectory: List[Tuple[int, float]] = [(start_step, tau)]

    def _save_ckpt(step_now: int):
        res_state = {
            "tau": None if not np.isfinite(tau) else float(tau),
            "controller": controller.state_dict() if controller else None,
            "telemetry": telemetry.state_dict(),
            "trajectory": [
                [int(s), (None if not np.isfinite(t) else float(t))]
                for s, t in (controller.trajectory if controller else trajectory)
            ],
        }
        ckpt.save(
            tcfg.ckpt_dir, {"params": params, "opt": opt_state}, step_now,
            extra={"resilience": res_state},
        )

    losses, sim_times, drops, host_times = [], [], [], []
    for step in range(start_step, tcfg.steps):
        if dist is None:
            mbs = microbatches_at(step, data_cfg, total_m)
            mbs = {k: jnp.asarray(v) for k, v in mbs.items() if k != "lengths"}
        else:
            b = batch_at(step, data_cfg)
            mbs = {k: jnp.asarray(b[k]) for k in ("tokens", "weights")}

        # --- latency draws for the N virtual workers (Algorithm 1 input) ---
        t = _latencies_at(tcfg, step, n, m)
        profile.append(t)

        # --- threshold selection -------------------------------------------
        # static: one-shot Algorithm 2 after the calibration window
        if (
            tcfg.auto_threshold
            and not tcfg.online_tau
            and tcfg.drop.enabled
            and not np.isfinite(tau)
            and step == tcfg.calibration_steps
        ):
            prof = np.stack(profile)  # (I, N, M)
            res = select_threshold(prof, tcfg.tc)
            tau = res.tau
            trajectory.append((step, tau))
            if bundle is not None:
                # tau is baked into the traced drop mask: rebuild (one
                # recompile per calibration, not per step)
                bundle = build_bundle(tau)

        # online: the controller re-estimates tau* from the rolling window
        if controller is not None:
            decision = controller.maybe_update(
                step, telemetry, steps_remaining=tcfg.steps - step
            )
            if decision.applied:
                tau = decision.tau
                trajectory.append((step, tau))
                if bundle is not None:
                    bundle = build_bundle(tau)

        # --- drop mask (per worker), flattened onto the microbatch axis ---
        if tcfg.drop.enabled and np.isfinite(tau):
            mask_nm = np.asarray(
                drop_mask(jnp.asarray(t), tau, tcfg.drop.min_microbatches)
            )
        else:
            mask_nm = np.ones((n, m), np.float32)

        # --- optionally turn the scenario into physical delay --------------
        if tcfg.inject_real_delays and hasattr(tcfg.latency, "host_delay_at"):
            worst = max(
                tcfg.latency.host_delay_at(step, r, n, m, seed=tcfg.seed + 1)
                for r in range(n)
            )
            if worst > 0:
                time.sleep(worst)

        h0 = time.monotonic()
        if bundle is not None:
            params, opt_state, metrics = bundle(params, opt_state, mbs, jnp.asarray(t))
            loss = metrics["loss"]
            stats = {"completed_fraction": metrics["completed_fraction"]}
        else:
            mask = jnp.asarray(mask_nm.reshape(total_m))
            params, opt_state, loss, stats = step_fn(params, opt_state, mbs, mask)
        jax.block_until_ready(loss)
        host_step_s = time.monotonic() - h0

        # --- simulated iteration time (eq. in §4.3) ---
        t_workers = (t * mask_nm).sum(axis=-1)  # compute actually performed
        t_iter = float(t_workers.max() + tcfg.tc) if tcfg.drop.enabled and np.isfinite(tau) else float(
            t.sum(axis=-1).max() + tcfg.tc
        )
        drop_frac = 1.0 - float(stats["completed_fraction"])
        losses.append(float(loss))
        sim_times.append(t_iter)
        drops.append(drop_frac)
        host_times.append(host_step_s)

        telemetry.record(
            step, t, host_step_s=host_step_s, tau=tau, drop_fraction=drop_frac
        )

        if tcfg.ckpt_dir and tcfg.ckpt_every and (step + 1) % tcfg.ckpt_every == 0:
            _save_ckpt(step + 1)

    final_trajectory = list(controller.trajectory) if controller else trajectory
    metrics: Dict[str, Any] = {
        "final_loss": losses[-1] if losses else float("nan"),
        "mean_drop": float(np.mean(drops)) if drops else 0.0,
        "total_sim_time": float(np.sum(sim_times)),
        "tau_changes": max(len(final_trajectory) - 1, 0),
        "bundle_rebuilds": (controller.rebuilds if controller else 0) if bundle is not None else 0,
    }
    if eval_fn is not None:
        metrics["eval"] = float(eval_fn(params))
    return TrainResult(
        params, losses, sim_times, drops, float(tau), metrics,
        tau_trajectory=final_trajectory, telemetry=telemetry.summary(),
        host_step_s=host_times,
    )
