"""Serve a small model with the chunked-prefill continuous batcher.

    PYTHONPATH=src python examples/serve.py --batch 8 --new-tokens 32 \
        --chunk-size 16 --token-budget 48

Initializes a small decoder and pushes a stream of requests through
``ContinuousBatcher``: prompts are prefilled ``--chunk-size`` tokens per
engine step, and each step's total work is capped at ``--token-budget``
scheduled tokens — the serving analogue of DropCompute's compute
threshold ``tau`` (overflow prefill chunks are deferred, decode slots
never stall).  ``--chunk-size 1`` reproduces the seed token-streaming
behaviour for comparison.
"""
import argparse
import os
import sys
import time

import jax

# the seeded workload helpers live with the benchmarks (one generator,
# one seed convention — benchmarks and examples replay identical sets)
sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    os.pardir, "benchmarks")
)
from common import make_requests  # noqa: E402

from repro.launch import compile_cache
from repro.models import ModelConfig
from repro.models.model import init_params
from repro.serve import (
    ContinuousBatcher,
    DraftModelProposer,
    NGramProposer,
    SamplingParams,
    SpecConfig,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8, help="cache slots")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--chunk-size", type=int, default=16)
    ap.add_argument("--token-budget", type=int, default=0,
                    help="per-step scheduled-token cap (0 = uncapped)")
    ap.add_argument("--packed", action="store_true",
                    help="token-packed step program: granted tokens alone "
                         "determine per-step compute")
    ap.add_argument("--cache", default="dense", choices=["dense", "paged"],
                    help="KV-cache layout (repro.serve.kv): paged = page "
                         "pool + block tables + prefix sharing")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--kv-dtype", default="", metavar="DTYPE",
                    help="paged-pool element type (e.g. 'int8': quantized "
                         "pages with per-row scales — about half the bytes "
                         "per page, so a fixed HBM budget holds ~2x the "
                         "pages; outputs are allclose to dense, not "
                         "bit-identical). Default: the model compute dtype")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="give every prompt the same N-token prefix; with "
                         "--cache paged, later requests map the first "
                         "one's pages instead of re-prefilling them. "
                         "Sharing needs the prefix pages to be fully "
                         "written first, so it kicks in for requests that "
                         "trail an earlier one (queued past the slot "
                         "count, or budget-staggered) — slots prefilling "
                         "the same prefix in lockstep each write their "
                         "own copy")
    ap.add_argument("--spec", default="off", choices=["off", "ngram", "draft"],
                    help="speculative decoding: 'ngram' proposes from each "
                         "request's own token history (prompt-lookup), "
                         "'draft' runs a smaller draft model ahead; the "
                         "target verifies k tokens per decode step and "
                         "output stays token-identical to plain greedy")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max draft tokens verified per decode slot per step")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy; with --spec, "
                         "rejection-sampling verification keeps the sampled "
                         "stream identical to no-spec decoding)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k truncation (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus truncation (1.0 = off)")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="base sampling seed; request i streams from "
                         "seed+i, so reruns are reproducible")
    ap.add_argument("--capacity-factor", type=float, default=0.0,
                    help="MoE serving dispatch (needs an MoE --arch, e.g. "
                         "moe_tiny or mixtral-8x22b): route tokens through "
                         "fixed per-expert buffers of ceil(cf * tokens * "
                         "top_k / n_experts) slots; overflow routes drop to "
                         "the residual path — per-expert tau.  0 = dense "
                         "dispatch (every token through every chosen "
                         "expert); inf = never drop, byte-identical to "
                         "dense")
    ap.add_argument("--arch", default="",
                    help="optional smoke-config name — any pattern serves "
                         "through this engine now: attention "
                         "(qwen2.5-3b), MoE (mixtral-8x22b, moe_tiny), "
                         "SSD (mamba2-130m, mamba2_tiny), RG-LRU hybrid "
                         "(recurrentgemma-2b, hybrid_tiny)")
    args = ap.parse_args()
    compile_cache.enable()

    if args.arch:
        from repro.configs import get_smoke_config

        cfg = get_smoke_config(args.arch)
    else:
        cfg = ModelConfig(name="serve-demo", n_layers=4, d_model=128, n_heads=4,
                          n_kv_heads=2, d_ff=256, vocab_size=1003,
                          sliding_window=64, layer_pattern="LG", dtype="float32",
                          remat=False)
    print(f"serving {cfg.name}: {cfg.param_count()/1e6:.1f}M params")

    params = init_params(jax.random.PRNGKey(0), cfg)
    max_len = args.prompt_len + args.new_tokens
    spec = None
    if args.spec == "ngram":
        spec = SpecConfig(NGramProposer(), k=args.spec_k)
    elif args.spec == "draft":
        # demo draft: a half-width model (random weights, so expect low
        # acceptance — a real deployment distills or shrinks the target)
        dcfg = ModelConfig(name="serve-draft", n_layers=2, d_model=64,
                           n_heads=4, n_kv_heads=2, d_ff=128,
                           vocab_size=cfg.vocab_size, sliding_window=64,
                           layer_pattern="LG", dtype="float32", remat=False)
        dparams = init_params(jax.random.PRNGKey(1), dcfg)
        spec = SpecConfig(
            DraftModelProposer(dparams, dcfg, args.batch, max_len),
            k=args.spec_k,
        )
    eng = ContinuousBatcher(
        params, cfg, batch_slots=args.batch, max_len=max_len,
        chunk_size=args.chunk_size,
        token_budget=args.token_budget or None,
        packed=args.packed,
        cache=args.cache, page_size=args.page_size,
        kv_dtype=args.kv_dtype or None,
        spec=spec,
        capacity_factor=args.capacity_factor or None,
    )

    sampling = None
    if args.temperature > 0:
        sampling = SamplingParams(temperature=args.temperature,
                                  top_k=args.top_k, top_p=args.top_p,
                                  seed=args.sample_seed)
        print(f"  sampling: T={args.temperature} top_k={args.top_k} "
              f"top_p={args.top_p} base seed {args.sample_seed}")
    for req in make_requests(args.requests, args.prompt_len, args.new_tokens,
                             cfg.vocab_size, seed=1,
                             shared_prefix=args.shared_prefix,
                             sampling=sampling):
        eng.submit(req)

    t0 = time.time()
    done = eng.run()
    dt = time.time() - t0

    s = eng.stats_summary()
    n_out = sum(len(r.output) for r in done.values())
    n_prompt = args.requests * args.prompt_len
    print(f"finished {len(done)}/{args.requests} requests in {dt:.2f}s "
          f"({eng.steps} engine steps)")
    print(f"  prompt tokens {n_prompt}  output tokens {n_out}  "
          f"total {(n_prompt + n_out)/dt:.1f} tok/s")
    print(f"  mean TTFT {s['mean_ttft']*1e3:.1f} ms   p99 TTFT {s['p99_ttft']*1e3:.1f} ms")
    print(f"  max step tokens {s['max_step_tokens']:.0f}  "
          f"deferred {s['deferred_tokens']:.0f}  "
          f"max step wall {s['max_step_wall']*1e3:.1f} ms")
    print(f"  mean step {s['mean_step_wall']*1e3:.2f} ms: host "
          f"{(s['mean_step_wall'] - s['mean_step_sync'])*1e3:.2f} ms, waiting on "
          f"the device {s['mean_step_sync']*1e3:.2f} ms")
    if eng.kv is not None:
        print(f"  paged KV: {s['peak_used_pages']:.0f}/{s['num_pages']:.0f} "
              f"peak pages used ({args.page_size} tokens each), "
              f"{s['shared_tokens']:.0f} prompt tokens served from "
              f"prefix-shared pages")
    if eng.spec is not None:
        print(f"  speculative ({args.spec}, k={args.spec_k}): "
              f"{s['draft_tokens']:.0f} drafts verified, acceptance "
              f"{s['acceptance_rate']:.2f}, "
              f"{s['steps_per_token']:.2f} engine steps per generated token")
    if args.capacity_factor:
        print(f"  MoE capacity dispatch (cf={args.capacity_factor}): "
              f"{s['expert_overflow_tokens']:.0f} routes dropped to the "
              f"residual path (max {s['max_expert_overflow']:.0f}/step)")
    r0 = done[0]
    print("sample continuation:", r0.output[:12])


if __name__ == "__main__":
    main()
