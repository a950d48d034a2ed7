"""Bring-up check: the trainer and the serving engine on a TPU.

    python chip_smoke.py             # one chip: kernel, train and serve phases
    python chip_smoke.py --chips 4   # four chips: the SPMD DropCompute step only

Everything runs in this one process; the script starts no other.

One chip, three phases, each at the full width of a configuration the
repo ships (weights random, from a seed):

* kernels -- ``paged_flash_attention`` (bf16 and int8 pools, internlm2-1.8b
  heads, the serve phase's pool) and ``ssd_segment`` (mamba2-130m heads)
  against their ``kernels/ref.py`` oracles;
* train -- ``repro.train.train`` on BERT-Large, seq 512, 8 virtual workers
  x 4 micro-batches of 8 rows, DropCompute with a finite tau: a warm-up
  step and five timed ones.  Losses must be finite and some step must
  drop micro-batches;
* serve -- ``ContinuousBatcher(packed=True, cache="paged")`` on
  internlm2-1.8b with 8 seeded requests, and the same engine over a
  dense cache.  Every request must finish with its ``max_new_tokens``,
  the compiled packed step must hold the Pallas kernel
  (``tpu_custom_call``), and every token either engine serves must be
  the argmax of a float32 reference forward pass, or within a bf16
  near-tie of it (``NEAR_TIE``).

``--chips 4`` runs BERT-Large through the trainer's SPMD path on a
4-device data mesh and on a one-device mesh: losses and completed
fractions must agree, the compiled step must all-reduce, and the batch
must be spread over 4 devices.

The ``setup:`` line reports compile time: the first train step's excess
over the median step, plus the serve phase's compiles of the engines'
step programs.  JAX's persistent compilation cache
(``repro.launch.compile_cache``) makes a second run's much shorter.  Any failed check exits non-zero; so does a
host where JAX finds no TPU.  The last line of standard output is one
JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "benchmarks")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from common import mixed_requests  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import DropConfig, LatencyModel, NoiseModel  # noqa: E402
from repro.data import DataConfig  # noqa: E402
from repro.data.synthetic import batch_at  # noqa: E402
from repro.dist import Distribution  # noqa: E402
from repro.dist.mesh import make_mesh  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.models import InputShape  # noqa: E402
from repro.models.model import forward, init_params  # noqa: E402
from repro.serve import ContinuousBatcher  # noqa: E402
from repro.serve import scheduler  # noqa: E402
from repro.train import TrainConfig, train  # noqa: E402

# the paper's simulated per-micro-batch latency (appendix B.1) and a tau
# below the mean worker time, so that some micro-batches drop
LATENCY = LatencyModel(base=0.45, noise=NoiseModel(kind="paper_lognormal"))
TAU = 2.6

# serving shapes shared by the serve phase and the paged-kernel check
SERVE_SLOTS, SERVE_MAX_LEN, PAGE_SIZE = 8, 512, 16
SERVE_CHUNK, SERVE_BUDGET = 64, 128
# how far (in standard deviations of the reference logits) a served token
# may fall below the float32 reference's maximum: bf16 near-ties
NEAR_TIE = 0.1


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"FAIL: {what}")


def tpu_devices():
    try:
        devices = jax.devices()
    except RuntimeError as e:  # no backend at all
        raise SystemExit(f"no TPU found: {e}")
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"no TPU found: JAX sees only {devices[0].platform} devices"
        )
    return devices


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def max_err(out, expect) -> tuple:
    out = np.asarray(out, np.float32)
    expect = np.asarray(expect, np.float32)
    check(np.isfinite(out).all(), "kernel output has non-finite values")
    return float(np.abs(out - expect).max()), float(np.abs(expect).max())


def paged_case(rng, cfg, tokens):
    """Packed queries over the serve phase's shuffled page pool: tables
    hold the unallocated sentinel past each slot's length and a few
    hostile entries, and some queries are padding."""
    slots, blocks = SERVE_SLOTS, SERVE_MAX_LEN // PAGE_SIZE
    num_pages = slots * blocks
    q = rng.standard_normal((tokens, cfg.n_heads, cfg.hd), np.float32)
    kv_shape = (num_pages, PAGE_SIZE, cfg.n_kv_heads, cfg.hd)
    k = rng.standard_normal(kv_shape, np.float32)
    v = rng.standard_normal(kv_shape, np.float32)
    tables = rng.permutation(num_pages).reshape(slots, blocks)
    lens = rng.integers(1, SERVE_MAX_LEN + 1, slots)
    for s in range(slots):
        tables[s, -(-lens[s] // PAGE_SIZE):] = num_pages  # unallocated
    tables[0, 0] = -3  # hostile entries mask like the sentinel
    tables[1, 1] = num_pages + 7
    q_slots = rng.integers(0, slots, tokens)
    q_slots[-tokens // 8:] = -1  # padding
    q_pos = np.array([rng.integers(0, lens[s]) for s in np.maximum(q_slots, 0)])
    return q, k, v, tables, q_pos, q_slots


def kernel_phase(interpret: bool = False) -> None:
    rng = np.random.default_rng(0)
    # as many query tokens as the serve phase's packed step holds
    q, k, v, tables, q_pos, q_slots = paged_case(
        rng, get_config("internlm2_1_8b"), SERVE_BUDGET + 1)
    num_pages = k.shape[0]
    addr = tuple(jnp.asarray(a, jnp.int32) for a in (tables, q_pos, q_slots))
    qb, kb, vb = (jnp.asarray(a, jnp.bfloat16) for a in (q, k, v))
    with jax.default_matmul_precision("highest"):
        expect = ref.paged_attention_ref(qb.astype(jnp.float32), kb, vb, *addr)
    out = ops.paged_flash_attention(qb, kb, vb, *addr, interpret=interpret)
    err, scale = max_err(out, expect)
    log(f"kernels: paged_flash_attention bf16 pool ({num_pages} pages x "
        f"{PAGE_SIZE}) max-abs error {err!r} (|ref| max {scale!r})")
    check(err <= 2e-2, "paged_flash_attention (bf16 pool) disagrees with its oracle")

    # int8 pool: per-row symmetric codes + f32 scales, as the serving
    # write path stores them
    def quantize(pool):
        scale = np.abs(pool).max(-1) / 127.0
        codes = np.clip(np.round(pool / scale[..., None]), -127, 127)
        return jnp.asarray(codes, jnp.int8), jnp.asarray(scale, jnp.float32)

    (kq, ks), (vq, vs) = quantize(k), quantize(v)
    with jax.default_matmul_precision("highest"):
        expect = ref.paged_attention_ref(
            qb.astype(jnp.float32), kq, vq, *addr, k_scale=ks, v_scale=vs)
    out = ops.paged_flash_attention(
        qb, kq, vq, *addr, k_scale=ks, v_scale=vs, interpret=interpret)
    err, scale = max_err(out, expect)
    log(f"kernels: paged_flash_attention int8 pool max-abs error {err!r} "
        f"(|ref| max {scale!r})")
    check(err <= 2e-2, "paged_flash_attention (int8 pool) disagrees with its oracle")

    # ssd_segment at mamba2-130m widths: three requests then padding
    ssm_cfg = get_config("mamba2_130m")
    h = ssm_cfg.ssm_expand * ssm_cfg.d_model // ssm_cfg.ssm_head_dim
    p, n, t = ssm_cfg.ssm_head_dim, ssm_cfg.ssm_state, 256
    seg = np.full(t, -1, np.int32)
    seg[0:100], seg[100:130], seg[130:230] = 3, 0, 5
    dt = rng.uniform(0.01, 0.1, (t, h)).astype(np.float32)
    dt[seg < 0] = 0.0  # the model zeroes dt on padding
    a = rng.uniform(0.5, 2.0, h).astype(np.float32)
    ins = (
        rng.standard_normal((t, h, p), np.float32),
        dt,
        np.cumsum(dt * a, axis=0),
        rng.standard_normal((t, n), np.float32) * n ** -0.25,
        rng.standard_normal((t, n), np.float32) * n ** -0.25,
        seg,
    )
    ins = tuple(jnp.asarray(x) for x in ins)
    with jax.default_matmul_precision("highest"):
        expect = ref.ssd_segment_ref(*ins)
    out = ops.ssd_segment(*ins, interpret=interpret)
    err, scale = max_err(out, expect)
    log(f"kernels: ssd_segment (T={t}, H={h}, P={p}, N={n}) max-abs error "
        f"{err!r} (|ref| max {scale!r})")
    check(err <= 1e-2 * max(scale, 1.0), "ssd_segment disagrees with its oracle")
    check(not np.asarray(out)[seg < 0].any(), "ssd_segment padding rows are not zero")


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def train_phase(cfg, *, seq=512, workers=8, microbatches=4, rows=8, steps=6) -> float:
    """Returns the seconds the first step took beyond the median step:
    its compilation."""
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                      batch_size=workers * microbatches * rows, seed=0)
    tcfg = TrainConfig(
        steps=steps, n_workers=workers, microbatches=microbatches,
        drop=DropConfig(enabled=True, tau=TAU), latency=LATENCY, seed=0,
    )
    log(f"train: {cfg.name} ({cfg.param_count() / 1e6:.1f}M params, "
        f"{cfg.dtype} compute) seq {seq}, {workers} workers x {microbatches} "
        f"micro-batches of {rows}, tau {TAU}")
    r = train(cfg, data, tcfg)
    completed = [1.0 - d for d in r.drop_fractions]
    timed = r.host_step_s[1:]
    log(f"train: losses {r.losses}")
    log(f"train: completed_fraction {completed}")
    log(f"train: step time (host clock, after block_until_ready) median "
        f"{statistics.median(timed)!r} s over {len(timed)} steps; first "
        f"step (compilation included) {r.host_step_s[0]!r} s")
    check(all(np.isfinite(r.losses)), "a training loss is not finite")
    check(min(completed) < 1.0, "no step dropped a micro-batch")
    return r.host_step_s[0] - statistics.median(timed)


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def compile_packed_steps(eng) -> tuple:
    """AOT-compile the engine's two packed programs (mixed and pure-decode
    capacity); the engine's own calls then reuse them.  Returns (seconds,
    HLO text of the mixed program)."""
    t0 = time.perf_counter()
    hlo = None
    for cap in (eng.packed_capacity, eng.packed_decode_capacity):
        vec = jax.ShapeDtypeStruct((cap,), jnp.int32)
        compiled = scheduler._packed_engine_step.lower(
            eng.params, eng.cfg, eng.cache, vec, vec, vec, moe_impl=eng.moe_impl
        ).compile()
        hlo = hlo or compiled.as_text()
    return time.perf_counter() - t0, hlo


def reference_margins(params, cfg, prompts, outputs) -> tuple:
    """Teacher-forced plain forward pass (float32 compute, highest matmul
    precision) over each prompt and the tokens an engine generated.

    Returns ``(margins, spread)``, both ``(requests, new_tokens)``:
    how far each generated token's reference logit falls below the
    reference maximum at its position (0 = the reference argmax), and the
    standard deviation of the reference logits there.
    """
    ref_cfg = dataclasses.replace(cfg, dtype="float32", remat=False)
    seqs = [p + o[:-1] for p, o in zip(prompts, outputs)]
    toks = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
    for i, s in enumerate(seqs):
        toks[i, : len(s)] = s
    n = len(outputs[0])
    cols = np.array([[len(p) - 1 + j for j in range(n)] for p in prompts])

    @jax.jit
    def margins(params, toks, cols, targets):
        logits, _ = forward(params, ref_cfg, {"tokens": toks})
        sel = jnp.take_along_axis(logits, cols[..., None], axis=1)  # (R, N, V)
        chosen = jnp.take_along_axis(sel, targets[..., None], axis=-1)[..., 0]
        return sel.max(-1) - chosen, sel.std(-1)

    with jax.default_matmul_precision("highest"):
        out = margins(params, toks, cols, np.asarray(outputs, np.int32))
    return tuple(np.asarray(x) for x in out)


def serve_phase(cfg, *, requests=8, prompt_len=256, new_tokens=32) -> float:
    """Returns the seconds spent compiling the engines' step programs."""
    params = init_params(jax.random.PRNGKey(0), cfg)
    log(f"serve: {cfg.name} ({cfg.param_count() / 1e6:.1f}M params) "
        f"{requests} requests, prompts {prompt_len // 4}/{prompt_len} tokens, "
        f"{new_tokens} new tokens each")
    prompts = [r.prompt for r in mixed_requests(
        requests, prompt_len, new_tokens, cfg.vocab_size, seed=1)]
    outputs, setup = {}, 0.0
    for cache in ("paged", "dense"):
        eng = ContinuousBatcher(
            params, cfg, batch_slots=SERVE_SLOTS, max_len=SERVE_MAX_LEN,
            chunk_size=SERVE_CHUNK, token_budget=SERVE_BUDGET, packed=True,
            cache=cache, page_size=PAGE_SIZE,
        )
        compile_s, hlo = compile_packed_steps(eng)
        setup += compile_s
        if cache == "paged":
            check("tpu_custom_call" in hlo,
                  "the compiled packed paged step holds no Pallas kernel")
        for req in mixed_requests(requests, prompt_len, new_tokens,
                                  cfg.vocab_size, seed=1):
            eng.submit(req)
        t0 = time.perf_counter()
        done = eng.run()
        wall = time.perf_counter() - t0
        outputs[cache] = {uid: list(r.output) for uid, r in done.items()}
        log(f"serve: cache={cache} compile {compile_s!r} s, run {wall!r} s "
            f"(host clock), {eng.steps} engine steps"
            + (f", {eng.kv.num_pages} pages" if eng.kv is not None else ""))
        check(len(done) == requests, f"{cache}: {len(done)}/{requests} finished")
        for r in done.values():
            check(len(r.output) == new_tokens and not r.truncated,
                  f"{cache}: request {r.uid} gave {len(r.output)} tokens")
        del eng, done
    paged, dense = outputs["paged"], outputs["dense"]
    same = sum(a == b for u in dense for a, b in zip(paged[u], dense[u]))
    first_same = sum(paged[u][0] == dense[u][0] for u in dense)
    log(f"serve: paged vs dense token match {same / (requests * new_tokens)!r} "
        f"({same}/{requests * new_tokens}); first tokens equal for "
        f"{first_same}/{requests}")
    # Both engines compute in bf16 along different attention paths, so
    # where the model's top two logits nearly tie they may pick either,
    # and the streams part from there on.  So each engine's every token
    # is held to the float32 reference, given that engine's own prefix:
    # it may fall below the reference maximum by at most NEAR_TIE
    # standard deviations of the reference logits.  Where the top two are
    # further apart than that, it must be the reference argmax; and so
    # must both engines' first tokens, which then are equal.
    for cache in ("paged", "dense"):
        outs = [outputs[cache][u] for u in range(requests)]
        margin, spread = reference_margins(params, cfg, prompts, outs)
        rel = margin / spread
        worst = np.unravel_index(rel.argmax(), rel.shape)
        log(f"serve: cache={cache} vs float32 reference (margin below its "
            f"maximum, in logit std): first tokens {rel[:, 0].tolist()}; "
            f"all tokens max {float(rel.max())!r} at request {worst[0]} "
            f"token {worst[1]}")
        check(rel.max() <= NEAR_TIE,
              f"{cache}: request {worst[0]}'s token {worst[1]} is "
              f"{float(rel.max())!r} logit std below the reference maximum")
    return setup


# ---------------------------------------------------------------------------
# four chips: the SPMD trainer against one device
# ---------------------------------------------------------------------------


def mesh_phase(cfg, n_chips: int, *, seq=512, workers=4, microbatches=4,
               rows=4, steps=4) -> float:
    """Returns the seconds the first wide-mesh step took beyond the
    median step: its compilation."""
    devices = jax.devices()
    check(len(devices) >= n_chips, f"{n_chips} chips wanted, {len(devices)} found")
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                      batch_size=workers * microbatches * rows, seed=0)
    drop = DropConfig(enabled=True, tau=TAU)
    wide = Distribution.from_spec(str(n_chips))
    one = Distribution(make_mesh((1,), ("data",), devices=devices[:1]))
    results = {}
    for name, dist in (("wide", wide), ("one", one)):
        tcfg = TrainConfig(steps=steps, n_workers=workers,
                           microbatches=microbatches, drop=drop,
                           latency=LATENCY, seed=0, mesh=dist)
        results[name] = r = train(cfg, data, tcfg)
        log(f"mesh: {dist.n_devices} device(s): losses {r.losses}, completed "
            f"{[1.0 - d for d in r.drop_fractions]}, step time (host clock) "
            f"median {statistics.median(r.host_step_s[1:])!r} s, first "
            f"{r.host_step_s[0]!r} s")
    w, o = results["wide"], results["one"]
    rel = float(np.max(np.abs(np.subtract(w.losses, o.losses)) / np.abs(o.losses)))
    log(f"mesh: max relative loss difference {rel!r}")
    check(all(np.isfinite(w.losses)), "a training loss is not finite")
    check(rel <= 1e-2, "losses on the wide mesh disagree with one device")
    check(w.drop_fractions == o.drop_fractions,
          "completed fractions differ between the meshes")
    check(max(w.drop_fractions) > 0.0, "no step dropped a micro-batch")

    # the same step the trainer built: its program and its batch placement
    shape = InputShape("train_cli", seq, data.batch_size, "train",
                       microbatches=microbatches)
    tc = TrainConfig()
    bundle = wide.train_step(cfg, shape, drop, n_workers=workers,
                             optimizer=tc.optimizer, lr=tc.lr,
                             clip_norm=tc.clip_norm, weight_decay=tc.weight_decay)
    hlo = bundle.lower().compile().as_text()
    n_ar = hlo.count("all-reduce(") + hlo.count("all-reduce-start(")
    batch = jax.device_put(
        {k: v for k, v in batch_at(0, data).items() if k != "lengths"},
        bundle.in_shardings[2],
    )
    on = sorted({s.device.id for s in batch["tokens"].addressable_shards})
    log(f"mesh: compiled step holds {n_ar} all-reduce(s); batch shards on "
        f"devices {on}")
    check(n_ar > 0, "the compiled SPMD step holds no all-reduce")
    check(len(on) == n_chips, f"batch sits on {len(on)} devices, not {n_chips}")
    return w.host_step_s[0] - statistics.median(w.host_step_s[1:])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = run only the SPMD trainer on a 4-device mesh "
                         "against one device")
    args = ap.parse_args()

    compile_cache.enable()  # before the first compile
    devices = tpu_devices()
    log(f"device: {devices[0].device_kind} x {len(devices)}")
    if args.chips == 4:
        setup = mesh_phase(get_config("bert_large"), 4)
        log(f"setup: {setup!r} s compiling (first wide-mesh step beyond the "
            f"median)")
    else:
        t0 = time.perf_counter()
        kernel_phase()
        log(f"kernels: phase took {time.perf_counter() - t0!r} s")
        train_s = train_phase(get_config("bert_large"))
        serve_s = serve_phase(get_config("internlm2_1_8b"))
        log(f"setup: {train_s + serve_s!r} s compiling (first train step "
            f"beyond the median {train_s!r}, serve step programs "
            f"{serve_s!r})")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
