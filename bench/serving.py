"""What the two serving drivers share: the engine as a cell configures it,
the seams around its step, warm-up, and the check against the plain
reference."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .common import Spans, log, reference_module, rng
from .weights import make_params


def build_engine(cfg, conf: dict, mix: dict, seed: int):
    from repro.serve import ContinuousBatcher

    e = mix["engine"]
    params = make_params(cfg, conf, seed)
    eng = ContinuousBatcher(
        params, cfg, batch_slots=e["slots"], max_len=e["max_len"],
        chunk_size=e["chunk_size"], token_budget=e["token_budget"],
        packed=True, cache="paged", page_size=e["page_size"],
        num_pages=e["num_pages"],
    )
    return eng, params


def add_seams(eng, spans: Spans, steps: list) -> None:
    """Host spans around ``step`` (the engine's host loop) and around
    ``_run_packed`` (layout, dispatch, the device step and its sync); the
    latter also records each step's grants, for the FLOP and byte
    counts."""
    step, run_packed = eng.step, eng._run_packed

    def step_seam():
        with spans.span("step"):
            step()

    def run_packed_seam(grants, out_base):
        rec = {"grants": [(int(s), int(p), len(t)) for s, p, t in grants]}
        with spans.span("model_step"):
            out = run_packed(grants, out_base)
        rec["t0"], rec["t1"] = spans.records[-1][1:]
        steps.append(rec)
        return out

    eng.step = step_seam
    eng._run_packed = run_packed_seam


def warm_up(eng, mix: dict, vocab: int) -> None:
    """Compile every program the cell's traffic drives: the mixed and the
    decode-only packed steps, each with the greedy sampler and, where the
    mix samples, the top-p sampler."""
    from repro.serve import Request, SamplingParams

    samp = mix.get("sampling", {})
    params = [SamplingParams()]
    if samp.get("temperature", 0.0) > 0:
        params.append(SamplingParams(temperature=samp["temperature"],
                                     top_p=samp.get("top_p", 1.0), seed=1))
    r = np.random.default_rng(0)
    for i, sp in enumerate(params):
        prompt = r.integers(0, vocab, mix["engine"]["chunk_size"] + 3).tolist()
        eng.submit(Request(uid=-1 - i, prompt=prompt, max_new_tokens=3, sampling=sp))
        eng.run()
    eng.reset_stats()


@jax.jit
def _gaps(logits, served, n):
    """The gap of each served token below the reference's best logit, in
    units of the reference logits' standard deviation at its position;
    0 past the first ``n`` positions."""
    valid = jnp.arange(logits.shape[0]) < n
    chosen = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
    gap = (logits.max(-1) - chosen) / logits.std(-1)
    return jnp.where(valid, gap, 0.0)


def sample_for_check(done: list, k: int, seed: int) -> list:
    """``k`` finished greedy requests: the longest, then others in an
    order drawn from the seed.  Only greedy tokens are compared: a sampled
    token's gap below the best says nothing about its correctness."""
    greedy = [r for r in done if r["temperature"] == 0.0]
    if not greedy:
        return []
    longest = max(greedy, key=lambda r: r["prompt_len"] + r["n_out"])
    order = rng(seed, 3).permutation(len(greedy))
    return ([longest] + [greedy[i] for i in order if greedy[i] is not longest])[:k]


def _reference_inputs(r: dict, lmax: int, kmax: int):
    """One served request as the reference reads it: the prompt and the
    served tokens (padded to ``lmax``), the positions whose next-token
    logits produced each served token, and the served tokens (both padded
    to ``kmax``)."""
    seq = r["prompt"] + r["output"][:-1]
    toks = np.zeros(lmax, np.int32)
    toks[: len(seq)] = seq
    n = len(r["output"])
    at = np.zeros(kmax, np.int32)
    at[:n] = np.arange(r["prompt_len"] - 1, r["prompt_len"] - 1 + n)
    served = np.zeros(kmax, np.int32)
    served[:n] = r["output"]
    return jnp.asarray(toks), jnp.asarray(at), jnp.asarray(served), n


def check_against_reference(params, config_name: str, model: dict, mix: dict,
                            sample: list) -> dict:
    """The served tokens of ``sample`` against the float32 reference: the
    widest gap of a served token (``gap_std``), the mean gap over every
    compared token (``mean_gap_std``) and the tokens compared."""
    ref = reference_module(config_name)
    gap, total, n_tokens = 0.0, 0.0, 0
    for r in sample:
        toks, at, served, n = _reference_inputs(r, mix["engine"]["max_len"],
                                                mix["output"]["max"])
        logits = ref.logits_at(params, toks, at, model)
        g = np.asarray(_gaps(logits, served, n))
        gap, total, n_tokens = max(gap, float(g.max())), total + float(g.sum()), n_tokens + n
    return {"gap_std": gap, "mean_gap_std": total / max(n_tokens, 1), "tokens": n_tokens}


def control_gaps(params, config_name: str, model: dict, mix: dict,
                 sample: list) -> dict:
    """The control's gaps on the positions of ``sample``: at each, the
    token that the reference computed with float8 products ranks first,
    measured as ``check_against_reference`` measures a served token."""
    ref = reference_module(config_name)
    gap, total, n_tokens = 0.0, 0.0, 0
    for r in sample:
        toks, at, _, n = _reference_inputs(r, mix["engine"]["max_len"],
                                           mix["output"]["max"])
        logits = ref.logits_at(params, toks, at, model)
        pick = jnp.argmax(ref.logits_at(params, toks, at, model, quant="fp8"), -1)
        g = np.asarray(_gaps(logits, pick, n))
        gap, total, n_tokens = max(gap, float(g.max())), total + float(g.sum()), n_tokens + n
    return {"gap_std": gap, "mean_gap_std": total / max(n_tokens, 1)}


def request_log(reqs, engine_reqs: dict) -> list:
    """One record per generated request, joined with the engine's."""
    out = []
    for q in reqs:
        e = engine_reqs.get(q.uid)
        out.append({
            "uid": q.uid, "prompt": q.prompt, "prompt_len": len(q.prompt),
            "max_new": q.max_new, "temperature": q.temperature, "top_p": q.top_p,
            "group": q.group,
            "output": list(e.output) if e is not None else [],
            "n_out": len(e.output) if e is not None else 0,
            "admitted": None if e is None else e.admitted_at,
            "engine_first": None if e is None else e.first_token_at,
            "engine_finished": None if e is None else e.finished_at,
        })
    return out


def checks_from(measured: dict, limits: dict) -> dict:
    log(f"check: compared {measured['tokens']} served greedy tokens")
    return {"gap_std": (measured["gap_std"], limits["gap_std"])}
