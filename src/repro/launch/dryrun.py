import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combo.

Proves the distribution config is coherent without hardware: builds the
production mesh from 512 host placeholder devices, jits the real
train/prefill/serve step with the real sharding rules, and records

  * memory_analysis()   — per-device bytes (proves it fits 16 GB v5e HBM),
  * cost_analysis()     — HLO FLOPs / bytes for the roofline terms,
  * the collective schedule parsed from the compiled HLO (all-gather /
    all-reduce / reduce-scatter / all-to-all / collective-permute bytes).

Results are cached as JSON under benchmarks/results/dryrun/ so reruns
only compile what changed.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch mamba2-130m --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--force]
"""
import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ARCHITECTURES, get_config
from repro.launch import hlo_cost
from repro.core.dropcompute import DropConfig
from repro.dist import HW, Distribution
from repro.launch import steps as S
from repro.models import INPUT_SHAPES

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"

# long_500k needs sub-quadratic attention: only SSM / hybrid / SWA archs
# run it (see DESIGN.md §long-context).  Encoder-only (bert) has no decode.
LONG_CONTEXT_ARCHS = {"mamba2_130m", "recurrentgemma_2b", "mixtral_8x22b", "gemma3_27b"}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_SHAPE_RE = re.compile(r"(bf16|f64|f32|f16|f8\w*|s64|s32|s16|s8|u64|u32|u16|u8|pred)\[([\d,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        key = dt[:4] if dt.startswith("f8") else dt
        total += n * _DTYPE_BYTES.get(key, 4)
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Sum output bytes of every collective op in the compiled HLO."""
    out = {c: {"count": 0, "bytes": 0} for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+([\w\-]+)\(", ls)
        if not m:
            continue
        shapes, opcode = m.group(1), m.group(2)
        base = opcode.rstrip("-start").rstrip("-done") if opcode.endswith(("-start", "-done")) else opcode
        for c in _COLLECTIVES:
            if base == c or opcode == c or opcode == c + "-start":
                if opcode.endswith("-done"):
                    break  # avoid double counting start/done pairs
                out[c]["count"] += 1
                out[c]["bytes"] += _shape_bytes(shapes)
                break
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items() if isinstance(v, dict))
    return out


def lower_combo(
    arch: str,
    shape_name: str,
    dist: Distribution,
    multi_pod: bool,
    drop_tau: float = float("inf"),
    cast_once: bool = False,
    microbatches: int = 0,
    lower_only: bool = False,
):
    """Lower (+ compile, unless ``lower_only``) one (arch, shape, mesh)
    through the ``repro.dist`` step builders. Returns result dict.

    ``cast_once``/``microbatches`` are §Perf hillclimb knobs.
    """
    import dataclasses

    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if shape.mode == "train" and get_config(arch).param_count() > 50e9 and not multi_pod:
        # single-pod giants: 16 accumulations (paper uses 12) halve the
        # per-micro-batch activation footprint
        shape = dataclasses.replace(shape, microbatches=16)
    if microbatches and shape.mode == "train":
        shape = dataclasses.replace(shape, microbatches=microbatches)

    t0 = time.time()
    moe_impl = "spmd" if cfg.n_experts > 0 else "sort"
    # >50B models: bf16 Adam moments + bf16 grad accumulators — required
    # to fit 16 GB/chip state bytes at 235B params / 256 chips (see
    # EXPERIMENTS.md §Dry-run notes).
    big = cfg.param_count() > 50e9
    dt = jnp.bfloat16 if big else jnp.float32
    if shape.mode == "train":
        drop = DropConfig(enabled=True, tau=drop_tau, normalize="computed")
        bundle = dist.train_step(
            cfg, shape, drop, moe_impl=moe_impl,
            state_dtype=dt, accum_dtype=dt, cast_params_once=cast_once,
        )
    elif shape.mode == "prefill":
        bundle = dist.prefill_step(cfg, shape, moe_impl=moe_impl)
    else:  # decode
        bundle = dist.serve_step(cfg, shape)
    lowered = bundle.lower()
    t_lower = time.time() - t0

    if lower_only:
        return {
            "arch": arch,
            "shape": shape_name,
            "mesh": "x".join(str(s) for s in dist.mesh.devices.shape),
            "mode": shape.mode,
            "lower_s": round(t_lower, 1),
            "lower_only": True,
        }

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    walked = hlo_cost.analyze(hlo)  # trip-count-aware (scans multiplied)

    mesh = dist.mesh
    n_chips = mesh.devices.size
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "multi_pod": multi_pod,
        "n_chips": int(n_chips),
        "mode": shape.mode,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "generated_code_bytes": int(getattr(mem, "generated_code_size_in_bytes", 0)),
        },
        "cost": {
            k: float(v)
            for k, v in cost.items()
            if isinstance(v, (int, float)) and "{" not in k
        },
        # trip-count-aware walk of the compiled HLO (per-device numbers):
        "walked": walked,
        "collectives": coll,
        "param_count": get_config(arch).param_count(),
        "active_param_count": get_config(arch).active_param_count(),
        "hw": HW,
    }
    return result


def combos(include_long=True):
    for arch in ARCHITECTURES:
        cfg = get_config(arch)
        for shape_name in INPUT_SHAPES:
            if shape_name == "long_500k" and arch not in LONG_CONTEXT_ARCHS:
                continue
            yield arch, shape_name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--lower-only", action="store_true",
                    help="stop after lowering (no XLA compile) — CI smoke")
    ap.add_argument("--tag", default="", help="suffix for result files (perf iterations)")
    args = ap.parse_args()

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    if args.all:
        todo = list(combos())
    else:
        assert args.arch and args.shape, "--arch and --shape, or --all"
        todo = [(args.arch, args.shape)]

    failures = []
    for multi_pod in meshes:
        dist = Distribution.production(multi_pod=multi_pod)
        mesh_tag = "2x16x16" if multi_pod else "16x16"
        for arch, shape_name in todo:
            name = f"{arch}_{shape_name}_{mesh_tag}{args.tag}.json"
            out_path = RESULTS_DIR / name
            if out_path.exists() and not args.force and not args.lower_only:
                print(f"[skip] {name} (cached)")
                continue
            print(f"[run ] {arch} x {shape_name} on {mesh_tag} ...", flush=True)
            try:
                res = lower_combo(arch, shape_name, dist, multi_pod,
                                  lower_only=args.lower_only)
                if args.lower_only:
                    print(f"  ok: lowered in {res['lower_s']}s (no compile)")
                    continue
                out_path.write_text(json.dumps(res, indent=1))
                per_dev = res["memory"]
                total_fit = (per_dev["output_bytes"] + per_dev["temp_bytes"] + per_dev["argument_bytes"])
                print(
                    f"  ok: compile {res['compile_s']}s, "
                    f"mem/dev {total_fit/2**30:.2f} GiB, "
                    f"flops {res['walked']['flops']:.3e}, "
                    f"coll {res['walked']['collective_bytes']/2**20:.1f} MiB, "
                    f"unkloops {res['walked']['unknown_trip_loops']}"
                )
            except Exception as e:
                failures.append((arch, shape_name, mesh_tag, repr(e)))
                print(f"  FAIL: {e!r}")
                traceback.print_exc(limit=3)

    if failures:
        print("\nFAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nAll dry-run combos %s successfully."
          % ("lowered" if args.lower_only else "compiled"))


if __name__ == "__main__":
    main()
