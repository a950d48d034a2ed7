"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and makes its requests from the seed.

Every seed gets the same multiset of sizes and gaps, in another order:
lengths are the quantiles ``(i + 0.5) / n`` of their log-normal, inter-
arrival gaps those of the exponential, and prefix groups those of the
Zipf law, each shuffled by the seed.  So two seeds differ in the order of
the work and in the token ids, not in how much work there is.  A mix
that sets ``order_seed`` shuffles by that number instead: every seed then
gets one schedule (the same lengths, groups, greedy requests and due
times, in the same order), and the seed draws the token ids and the
sampling seeds.  (Copied in
spirit from ``benchmarks/traffic_replay.py``, which draws i.i.d. sizes.)
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List, Optional

import numpy as np

from .common import rng


@dataclasses.dataclass
class Req:
    uid: int
    due: float  # seconds after the window opens
    prompt: List[int]
    max_new: int
    temperature: float = 0.0
    top_p: float = 1.0
    sample_seed: int = 0
    group: int = -1  # shared-prefix group, -1 = none

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: dict, n: int, r: np.random.Generator) -> np.ndarray:
    z = np.array([statistics.NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    x = np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    return r.permutation(x)


def exponential_gaps(n: int, seconds: float, r: np.random.Generator) -> np.ndarray:
    gaps = -np.log1p(-_quantiles(n))
    gaps = r.permutation(gaps) * (seconds / gaps.sum())
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def zipf_groups(groups: int, a: float, n: int, r: np.random.Generator) -> np.ndarray:
    p = 1.0 / np.arange(1, groups + 1) ** a
    cdf = np.cumsum(p / p.sum())
    g = np.searchsorted(cdf, _quantiles(n), side="right")
    return r.permutation(np.minimum(g, groups - 1))


def requests(mix: dict, seed: int, seconds: float, vocab: int,
             n: Optional[int] = None) -> List[Req]:
    """The mix's requests for one run of ``seconds``.

    Open-loop mixes (``rate_rps``) send ``round(rate * seconds)`` requests
    due over the window; queued mixes (``queue``) have every request due
    at 0.
    """
    if n is None:
        n = (int(round(mix["rate_rps"] * seconds)) if "rate_rps" in mix
             else int(mix["queue"]))
    r = rng(mix.get("order_seed", seed), 0)
    due = (exponential_gaps(n, seconds, r) if "rate_rps" in mix
           else np.zeros(n))
    turn = lognormal_lengths(mix["prompt"], n, r)
    out = lognormal_lengths(mix["output"], n, r)
    sp = mix.get("shared_prefix")
    groups = (zipf_groups(sp["groups"], sp["zipf_a"], n, r) if sp
              else np.full(n, -1))
    prefixes = {}
    if sp:
        for g in range(sp["groups"]):
            prefixes[g] = rng(seed, 1, g).integers(0, vocab, sp["tokens"]).tolist()
    samp = mix.get("sampling", {})
    every = samp.get("greedy_every", 1)
    greedy = np.zeros(n, bool)
    greedy[r.permutation(n)[: n // every if every > 1 else n]] = True
    toks = rng(seed, 2)
    reqs = []
    for i in range(n):
        prompt = prefixes.get(int(groups[i]), []) + toks.integers(0, vocab, int(turn[i])).tolist()
        reqs.append(Req(
            uid=i, due=float(due[i]), prompt=prompt, max_new=int(out[i]),
            temperature=0.0 if greedy[i] else float(samp.get("temperature", 0.0)),
            top_p=1.0 if greedy[i] else float(samp.get("top_p", 1.0)),
            sample_seed=int(toks.integers(0, 2**31 - 1)),
            group=int(groups[i]),
        ))
    return reqs


def lateness(requests_log: List[dict]) -> dict:
    """How late the generator submitted, against each request's due time."""
    late = [r["submitted"] - r["due_at"] for r in requests_log if r.get("submitted") is not None]
    if not late:
        return {"p50_ms": math.nan, "max_ms": math.nan}
    return {"p50_ms": 1e3 * float(np.median(late)), "max_ms": 1e3 * float(np.max(late))}
