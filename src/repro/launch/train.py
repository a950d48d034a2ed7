"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m \
        --steps 20 --drop-compute --auto-threshold

Selects an architecture from the registry (``--arch``, full or ``--smoke``
reduced config), builds the data pipeline and the DropCompute trainer, and
runs.  On a multi-device system pass ``--mesh`` data,model dims (e.g.
``--mesh 4,2``, or ``--mesh 2,16,16`` for pod,data,model) to run the
sharded SPMD step via the ``repro.dist`` sharding rules; without it the
virtual-worker simulation path runs on a single device (the
physical-cluster behaviour is exercised by the dry-run,
``repro.launch.dryrun``).
"""
import argparse

import numpy as np

from repro.configs import ARCHITECTURES, PAPER_MODELS, get_config, get_smoke_config
from repro.core import DropConfig, LatencyModel, NoiseModel
from repro.data import DataConfig
from repro.dist import Distribution
from repro.launch import compile_cache
from repro.train import TrainConfig, train
from repro.train.resilience import SCENARIOS, make_scenario


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    help=f"one of {ARCHITECTURES + PAPER_MODELS}")
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="use the reduced same-family config (CPU-trainable)")
    ap.add_argument("--full-config", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "lamb", "lans", "sgd"])
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--drop-compute", action="store_true")
    ap.add_argument("--tau", type=float, default=float("inf"))
    ap.add_argument("--auto-threshold", action="store_true")
    ap.add_argument("--normalize", default="computed", choices=["computed", "nominal"])
    ap.add_argument("--noise", default="paper_lognormal")
    ap.add_argument("--tc", type=float, default=0.5)
    ap.add_argument("--faults", default="", choices=[""] + sorted(SCENARIOS),
                    help="seeded resilience fault scenario layered over the "
                         "latency model (pareto/lognormal/badnode/stall/none)")
    ap.add_argument("--fault-onset", type=int, default=None,
                    help="step where mid-run faults (ramp/badnode) begin")
    ap.add_argument("--online-tau", action="store_true",
                    help="re-estimate tau* online from rolling telemetry "
                         "(replaces the one-shot --auto-threshold calibration)")
    ap.add_argument("--inject-real-delays", action="store_true",
                    help="sleep the injected fault delays around the real "
                         "step (physical compute variance)")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--resume", default="",
                    help="checkpoint dir to resume from (params, opt state "
                         "AND the adapted tau-controller state)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default="",
                    help="mesh dims: 'data,model' (e.g. 4,2) or "
                         "'pod,data,model' (e.g. 2,16,16); empty = "
                         "single-device virtual-worker path")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    dist = Distribution.from_spec(args.mesh) if args.mesh else None
    print(f"[train] arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"family={cfg.family} pattern={cfg.layer_pattern}"
          + (f" mesh={'x'.join(map(str, dist.mesh.devices.shape))}" if dist else ""))

    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      batch_size=args.batch, strategy="pack", seed=args.seed)
    latency = LatencyModel(base=0.45, noise=NoiseModel(kind=args.noise))
    if args.faults:
        latency = make_scenario(args.faults, base=latency, seed=args.seed,
                                onset=args.fault_onset)
    tcfg = TrainConfig(
        steps=args.steps, n_workers=args.workers, microbatches=args.microbatches,
        optimizer=args.optimizer, lr=args.lr,
        drop=DropConfig(enabled=args.drop_compute, tau=args.tau, normalize=args.normalize),
        auto_threshold=args.auto_threshold and not args.online_tau,
        calibration_steps=min(20, args.steps // 2),
        online_tau=args.online_tau, inject_real_delays=args.inject_real_delays,
        latency=latency, tc=args.tc, seed=args.seed, mesh=dist,
        ckpt_dir=args.ckpt or None, ckpt_every=50 if args.ckpt else 0,
        resume_from=args.resume or None,
    )
    r = train(cfg, data, tcfg)
    print(f"[train] loss {r.losses[0]:.3f} -> {r.losses[-1]:.3f}  "
          f"sim time {r.metrics['total_sim_time']:.0f}s  "
          f"drop {np.mean(r.drop_fractions):.1%}  tau={r.tau}")
    if len(r.tau_trajectory) > 1:
        print("[train] tau trajectory: "
              + " -> ".join(f"{s}:{t:.2f}" if np.isfinite(t) else f"{s}:inf"
                            for s, t in r.tau_trajectory))


if __name__ == "__main__":
    main()
