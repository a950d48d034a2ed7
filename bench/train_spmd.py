"""Training driver: ``repro.train.train`` on a data-parallel mesh, the
trainer's own loop from first step to last.

The benchmark reaches into the loop only through two seams it already
calls once per step: the latency model's ``sample_at`` (which opens and
closes the window, and starts and stops the profiler) and the SPMD step
bundle that ``Distribution.train_step`` returns (whose first outputs are
kept for the check).  Set-up builds the step, drives it from the seed
through the job's first ``check_steps`` steps, and the window times the
steps that follow, trainer host loop included.  When the window has run
``seconds`` the seam ends the loop.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .common import Run, log, reference_module
from .trace import Tracer
from .weights import abstract_params, make_params


class WindowClosed(Exception):
    """Raised from the latency seam to end the trainer's loop."""


class LatencySeam:
    """Delegates ``sample_at`` to the job's latency model and keeps time."""

    def __init__(self, inner, job: dict, seconds: float, tracer, run: Run):
        self.inner, self.job, self.seconds = inner, job, seconds
        self.tracer, self.run = tracer, run
        self.draws, self.calls = {}, {}
        self.w0 = None

    def sample_at(self, step, workers, m, seed=0):
        now = time.perf_counter()
        self.calls[step] = now
        first = self.job["check_steps"]
        if step == first:
            self.w0 = now
            self.run.extra["compiles"].arm()
        elif step > first:
            tr = self.tracer
            if tr is not None and tr.t0 is None and now - self.w0 >= self.job["trace_after_s"]:
                tr.start()
                self.trace_from = step
            elif tr is not None and tr.t1 is None and tr.t0 is not None and \
                    step - self.trace_from >= self.job["trace_steps"]:
                tr.stop()
                self.trace_steps = step - self.trace_from
            if now - self.w0 >= self.seconds and (tr is None or tr.t1 is not None):
                self.run.extra["compiles"].disarm()
                self.run.window = (self.w0, now)
                self.run.extra["window_steps"] = step - first
                raise WindowClosed
        t = self.inner.sample_at(step, workers, m, seed=seed)
        self.draws[step] = np.asarray(t)
        return t


def leaf_norms(tree):
    """Norm of every leaf, and of every layer of a stacked leaf."""
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = x.astype(jnp.float32)
        if any(getattr(p, "key", None) == "groups" for p in path):
            out.append(jnp.sqrt(jnp.sum(x.reshape(x.shape[0], -1) ** 2, -1)))
        else:
            out.append(jnp.sqrt(jnp.sum(x ** 2))[None])
    return jnp.concatenate(out)


class StepSeam:
    """The trainer's step bundle, keeping what the check needs from its
    first outputs: each step's loss and kept fraction, the first
    gradient as AdamW received it (its first moment over 1 - b1), and the
    parameters' change over the first ``check_steps`` steps."""

    def __init__(self, bundle, rec: dict, check_steps: int, p0, spans):
        self.bundle, self.rec, self.check_steps, self.p0 = bundle, rec, check_steps, p0
        self.spans, self.between = spans, None
        self.k = 0

    def __getattr__(self, name):
        return getattr(self.bundle, name)

    def __call__(self, params, opt_state, mbs, lat):
        if self.between is not None:
            self.spans.end(self.between)
        with self.spans.span("dispatch"):
            params, opt_state, metrics = self.bundle(params, opt_state, mbs, lat)
        self.rec.setdefault("completed", []).append(metrics["completed_fraction"])
        if self.k < self.check_steps:
            self.rec.setdefault("loss", []).append(float(metrics["loss"]))
        if self.k == 0:
            self.rec["first_grad"] = np.asarray(
                jax.jit(leaf_norms)(opt_state["m"])) / (1 - 0.9)
        if self.k == self.check_steps - 1:
            delta = jax.jit(lambda a, b: leaf_norms(jax.tree.map(jnp.subtract, a, b)))
            self.rec["change"] = np.asarray(delta(params, self.p0))
            self.p0 = None
        self.k += 1
        # the trainer's host loop: the sync on the loss, telemetry, the
        # next step's batch, latency draw and drop mask
        self.between = self.spans.begin("between_steps")
        return params, opt_state, metrics


def _make_distribution(n: int, rec: dict, job: dict, spans):
    from repro.dist import Distribution
    from repro.dist.mesh import make_mesh

    @dataclasses.dataclass(frozen=True)
    class SeamDistribution(Distribution):
        rec: Any = None

        def train_step(self, cfg, shape, drop, **kw):
            return StepSeam(Distribution.train_step(self, cfg, shape, drop, **kw),
                            self.rec, job["check_steps"], self.rec.pop("p0"), spans)

    return SeamDistribution(make_mesh((n,), ("data",), devices=jax.devices()[:n]), rec=rec)


def gaps(prog: np.ndarray, ref: np.ndarray, floor_share: float = 1e-3):
    """Worst leaf's gap between two leaf-norm vectors, against the larger
    of the reference leaf's norm and the median leaf's; leaves whose
    reference norm is under ``floor_share`` of the median are left out."""
    med = float(np.median(ref))
    keep = ref >= floor_share * med
    g = np.abs(prog - ref)[keep] / np.maximum(ref[keep], med)
    return float(g.max()), int((~keep).sum())


def feed_faults(batches, rows: int, seq_len: int, vocab: int) -> int:
    """How many of the feed's guarantees the batches of the first steps
    break: the shape, the token range, weights of one on every token, no
    row repeated within a step or across steps (so each worker and each
    step gets rows of its own).  The reference reads the same batches, so
    a fault here would otherwise reach both sides alike."""
    faults = 0
    seen = set()
    for b in batches:
        tok, w = np.asarray(b["tokens"]), np.asarray(b["weights"])
        if tok.shape != (rows, seq_len) or w.shape != (rows, seq_len):
            faults += 1
            continue
        faults += not np.issubdtype(tok.dtype, np.integer)
        faults += not (tok.min() >= 0 and tok.max() < vocab)
        faults += not np.all(w == 1.0)
        keys = {r.tobytes() for r in tok}
        faults += len(keys) < rows or bool(keys & seen)
        seen |= keys
    return faults


def run(ctx) -> Run:
    from repro.core import DropConfig, LatencyModel, NoiseModel
    from repro.data import DataConfig
    from repro.train import TrainConfig, train

    run, job, seed = ctx.run, ctx.mix, ctx.seed
    cfg, n = ctx.cfg, ctx.workload["chips"]
    rec = {}
    dist = _make_distribution(n, rec, job, run.spans)
    shardings = dist.param_shardings(abstract_params(cfg))
    params = make_params(cfg, ctx.conf, seed, shardings=shardings)
    rec["p0"] = jax.tree.map(jnp.copy, params)
    batch = job["workers"] * job["microbatches"] * job["rows"]
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=job["seq_len"],
                      batch_size=batch, seed=seed)
    lat = job["latency"]
    inner = LatencyModel(base=lat["base"], noise=NoiseModel(kind=lat["noise"]))
    tracer = Tracer(ctx.trace_dir, 0) if ctx.traced else None
    run.extra["compiles"] = ctx.compiles
    seam = LatencySeam(inner, job, ctx.seconds, tracer, run)
    tcfg = TrainConfig(
        steps=10**9, n_workers=job["workers"], microbatches=job["microbatches"],
        optimizer=job["optimizer"], lr=job["lr"], weight_decay=job["weight_decay"],
        clip_norm=job["clip_norm"], seed=seed,
        drop=DropConfig(enabled=True, tau=job["tau"], normalize=job["normalize"]),
        latency=seam, mesh=dist,
    )
    try:
        train(cfg, data, tcfg, params=params)
    except WindowClosed:
        pass
    del params
    run.setup_s = run.window[0] - ctx.t_start
    first = job["check_steps"]
    steps = range(first, first + run.extra["window_steps"])
    completed = [float(x) for x in rec["completed"]]
    for s in steps:
        run.steps.append({"step": s, "t0": seam.calls[s], "t1": seam.calls[s + 1],
                          "kept_rows": completed[s] * batch,
                          "latencies": seam.draws[s].tolist()})
    if tracer is not None:
        run.trace = tracer.reduce()
        run.extra["trace_steps"] = seam.trace_steps
        run.extra["trace_window"] = (tracer.t0, tracer.t1)
    run.extra["attempted"] = (len(run.steps), 0)
    ctx.read_memory()
    rec.pop("completed")
    # the reference follows the first steps from the same seed and feed
    from repro.data.synthetic import batch_at

    ref = reference_module(ctx.workload["config"])
    p0 = make_params(cfg, ctx.conf, seed)
    batches = [batch_at(s, data) for s in range(first)]
    n_feed = feed_faults(batches, batch, job["seq_len"], cfg.vocab_size)
    losses, g_ref, d_ref = ref.train_steps(
        p0, batches, [seam.draws[s] for s in range(first)], job,
        cfg.n_heads, jax.devices()[:n])
    loss_rel = max(abs(a - b) / abs(b) for a, b in zip(rec["loss"], losses))
    g_gap, g_out = gaps(rec["first_grad"], g_ref)
    d_gap, d_out = gaps(rec["change"], d_ref)
    lim = job["check"]["limits"]
    run.extra["checks"] = {"feed_faults": (n_feed, 0),
                           "loss_rel": (loss_rel, lim["loss_rel"]),
                           "grad_gap": (g_gap, lim["grad_gap"]),
                           "update_gap": (d_gap, lim["update_gap"])}
    log(f"check: losses program {rec['loss']} reference {losses}; leaves left "
        f"out (reference norm under 1e-3 of the median): gradient {g_out}, "
        f"change {d_out}")
    return run
