"""Operations and bytes the algorithms need, from shapes alone.

These are model counts: what the mathematics of a step needs, never
what an implementation happens to do (no recomputation, no padding, no
relayout, no pool size).  ``launch/hlo_cost.py`` counts what a compiled
program does; it may cross-check these, never replace them.
"""
from __future__ import annotations

from typing import Iterable, Tuple

Grant = Tuple[int, int, int]  # (slot, first position, tokens)


def _dims(model: dict):
    d, h = model["d_model"], model["n_heads"]
    kv = model["n_kv_heads"]
    hd = model.get("head_dim") or d // h
    return d, h, kv, hd


def matmul_params(model: dict) -> int:
    """Weights a token meets in matrix products: every layer's attention
    and MLP projections, plus the output head (the embedding lookup is a
    gather, not a product)."""
    d, h, kv, hd = _dims(model)
    mlp = (3 if model.get("act", "swiglu") in ("swiglu", "geglu") else 2) * d * model["d_ff"]
    attn = d * (h + 2 * kv) * hd + h * hd * d
    return model["n_layers"] * (attn + mlp) + d * model["vocab_size"]


def attention_flops(model: dict, keys: int) -> int:
    """One query token's scores and weighted values over ``keys`` keys,
    in one layer."""
    _, h, _, hd = _dims(model)
    return 4 * h * hd * keys


def decode_step_flops(model: dict, grants: Iterable[Grant]) -> int:
    """Forward FLOPs of one packed serving step: each granted token at
    position p attends causally to p + 1 keys in every layer."""
    n_tok, attn = 0, 0
    for _, p0, n in grants:
        n_tok += n
        # sum over positions p0 .. p0+n-1 of (p + 1) keys
        keys = n * p0 + n * (n + 1) // 2
        attn += attention_flops(model, 1) * keys
    return 2 * matmul_params(model) * n_tok + model["n_layers"] * attn


def paged_attention_work(model: dict, grants: Iterable[Grant], kv_bytes: int = 2,
                         act_bytes: int = 2) -> Tuple[int, int]:
    """(FLOPs, bytes) one layer's paged attention needs in one step: the
    scores and weighted values of every query over its causal context,
    reading each active slot's live keys and values once, plus the queries
    and outputs.  The same count whatever the kernel does: the pool's size
    and layout never enter."""
    _, h, kv, hd = _dims(model)
    flops = byts = 0
    for _, p0, n in grants:
        keys = n * p0 + n * (n + 1) // 2
        flops += attention_flops(model, 1) * keys
        live = p0 + n  # keys of the slot up to its last query
        byts += 2 * live * kv * hd * kv_bytes + 2 * n * h * hd * act_bytes
    return flops, byts


def train_step_flops(model: dict, tokens: int, seq_len: int) -> int:
    """Forward and backward FLOPs of ``tokens`` tokens in sequences of
    ``seq_len`` with bidirectional attention (every token sees the whole
    sequence); recomputation is not counted."""
    attn = model["n_layers"] * attention_flops(model, seq_len)
    return 3 * (2 * matmul_params(model) + attn) * tokens


def roofline_s(flops: float, byts: float, peaks: dict) -> Tuple[float, str]:
    """Least time the chip needs for the work, and which peak bounds it."""
    t_c = flops / peaks["bf16_flops"]
    t_m = byts / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
