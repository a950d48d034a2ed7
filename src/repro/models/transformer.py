"""Block assembly: pattern-driven layer stacks with scan-over-groups.

The per-layer block sequence comes from ``cfg.pattern`` ('G'/'L'/'R'/'M',
see config.py).  Layers are organized as

    n_groups repetitions of the pattern unit   (params stacked, lax.scan)
  + a tail of (n_layers % unit) explicit layers (python loop)

so heterogeneous stacks (gemma3's 5 local :1 global, recurrentgemma's
2 recurrent : 1 local) still compile to a compact scanned HLO, while
homogeneous stacks degenerate to a plain scan over all layers.  The scan
body is rematerialized (``jax.checkpoint``) when cfg.remat.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from . import layers as L
from . import moe as MoE
from . import rglru as RG
from . import ssm as SSD

PyTree = Any


def constrain_activations(x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Shard the residual stream (B, S, d) as (data-axes, None, model).

    Sharding d_model across the model axis keeps the per-layer remat
    residuals (stacked across the layer scan for backward) 16x smaller on
    the production mesh, at the cost of per-block gather/psum collectives
    around the projections.  Measured on gemma3-27b train_4k: WITH the
    constraint 4.7s compute / 33s collective / 9.8 GiB per device; WITHOUT
    it the partitioner loses its anchor inside the layer scan and produces
    8.2s / 62s / 31 GiB — so the constraint stays on for every model (the
    "skip it for small models" hypothesis was tested and refuted; see
    EXPERIMENTS.md §Perf).  No-op without an active multi-device mesh.
    """
    from .moe import _current_mesh  # lazy: avoids cycle

    mesh = _current_mesh()
    if mesh is None or mesh.size == 1 or x.ndim != 3:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n = 1
    for a in dp:
        n *= mesh.shape[a]
    while dp and x.shape[0] % n != 0:
        dp = dp[1:]
        n = 1
        for a in dp:
            n *= mesh.shape[a]
    tp = "model" if "model" in mesh.axis_names and x.shape[-1] % mesh.shape["model"] == 0 else None
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(dp if dp else None, None, tp))
    )


# ---------------------------------------------------------------------------
# Single block
# ---------------------------------------------------------------------------


def init_block(rng, cfg: ModelConfig, kind: str, cross: bool = False) -> PyTree:
    ks = jax.random.split(rng, 6)
    p: Dict[str, PyTree] = {"norm1": L.init_norm(cfg)}
    if kind in ("G", "L", "B"):
        p["attn"] = L.init_attention(ks[0], cfg)
        p["norm2"] = L.init_norm(cfg)
        if cfg.n_experts > 0:
            p["moe"] = MoE.init_moe(ks[1], cfg)
        else:
            p["mlp"] = L.init_mlp(ks[1], cfg)
        if cross:
            p["cross_norm"] = L.init_norm(cfg)
            p["cross_attn"] = L.init_attention(ks[2], cfg)
    elif kind == "R":
        p["rglru"] = RG.init_rglru(ks[0], cfg)
        p["norm2"] = L.init_norm(cfg)
        p["mlp"] = L.init_mlp(ks[1], cfg)
    elif kind == "M":
        p["ssd"] = SSD.init_ssd(ks[0], cfg)
    else:
        raise ValueError(kind)
    return p


def apply_block(
    p: PyTree,
    x: jnp.ndarray,
    cfg: ModelConfig,
    kind: str,
    positions: jnp.ndarray,
    cache: Optional[PyTree] = None,
    decode_pos=None,
    enc_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    moe_impl: str = "sort",
    seq_lens=None,
    slot_ids=None,
    page_tables=None,
    page_size: int = 0,
) -> Tuple[jnp.ndarray, Optional[PyTree], jnp.ndarray, jnp.ndarray]:
    """Returns (x, new_cache, aux_loss, expert_overflow)."""
    aux = jnp.zeros((), jnp.float32)
    overflow = jnp.zeros((), jnp.int32)
    new_cache = cache
    if kind in ("G", "L", "B"):
        h = L.apply_norm(p["norm1"], x, cfg)
        attn_cache = None if cache is None else cache.get("attn")
        y, attn_cache = L.apply_attention(
            p["attn"], h, cfg, kind, positions, attn_cache, decode_pos=decode_pos,
            seq_lens=seq_lens, slot_ids=slot_ids, page_tables=page_tables,
            page_size=page_size,
        )
        x = x + y
        if enc_kv is not None and "cross_attn" in p:
            h = L.apply_norm(p["cross_norm"], x, cfg)
            y, _ = L.apply_attention(p["cross_attn"], h, cfg, "X", positions, cross_kv=enc_kv)
            x = x + y
        h = L.apply_norm(p["norm2"], x, cfg)
        if cfg.n_experts > 0:
            if moe_impl == "capacity":
                valid = None
                if seq_lens is not None:
                    valid = jnp.arange(h.shape[1])[None, :] < seq_lens[:, None]
                elif slot_ids is not None:
                    valid = (slot_ids >= 0)[None, :]
                y, aux, overflow = MoE.apply_moe_capacity(p["moe"], h, cfg, valid=valid)
            else:
                y, aux = MoE.apply_moe(p["moe"], h, cfg, impl=moe_impl)
        else:
            y = L.apply_mlp(p["mlp"], h, cfg)
        x = x + y
        if cache is not None:
            new_cache = dict(cache)
            new_cache["attn"] = attn_cache
    elif kind == "R":
        h = L.apply_norm(p["norm1"], x, cfg)
        rg_cache = None if cache is None else cache.get("rglru")
        y, rg_cache = RG.apply_rglru(
            p["rglru"], h, cfg, rg_cache, seq_lens=seq_lens, slot_ids=slot_ids
        )
        x = x + y
        h = L.apply_norm(p["norm2"], x, cfg)
        x = x + L.apply_mlp(p["mlp"], h, cfg)
        if cache is not None:
            new_cache = dict(cache)
            new_cache["rglru"] = rg_cache
    elif kind == "M":
        h = L.apply_norm(p["norm1"], x, cfg)
        ssd_cache = None if cache is None else cache.get("ssd")
        y, ssd_cache = SSD.apply_ssd(
            p["ssd"], h, cfg, ssd_cache, seq_lens=seq_lens, slot_ids=slot_ids
        )
        x = x + y
        if cache is not None:
            new_cache = dict(cache)
            new_cache["ssd"] = ssd_cache
    return x, new_cache, aux, overflow


def init_block_cache(
    cfg: ModelConfig, kind: str, batch: int, seq_len: int, cross: bool = False,
    linear: bool = False,
):
    c: Dict[str, PyTree] = {}
    if kind in ("G", "L", "B"):
        c["attn"] = L.init_attention_cache(cfg, kind, batch, seq_len, linear=linear)
    elif kind == "R":
        c["rglru"] = RG.init_rglru_cache(cfg, batch)
    elif kind == "M":
        c["ssd"] = SSD.init_ssd_cache(cfg, batch)
    return c


# ---------------------------------------------------------------------------
# Layer stack (grouped scan)
# ---------------------------------------------------------------------------


def _unit_and_groups(cfg: ModelConfig) -> Tuple[str, int, int]:
    unit = cfg.layer_pattern
    n_groups = cfg.n_layers // len(unit)
    tail = cfg.n_layers % len(unit)
    return unit, n_groups, tail


def init_stack(rng, cfg: ModelConfig, cross: bool = False) -> PyTree:
    unit, n_groups, tail = _unit_and_groups(cfg)
    groups = []
    for j, kind in enumerate(unit):
        rngs = jax.random.split(jax.random.fold_in(rng, j), n_groups)
        stacked = jax.vmap(lambda r: init_block(r, cfg, kind, cross))(rngs)
        groups.append(stacked)
    tail_ps = [
        init_block(jax.random.fold_in(rng, 1000 + i), cfg, cfg.pattern[n_groups * len(unit) + i], cross)
        for i in range(tail)
    ]
    return {"groups": tuple(groups), "tail": tail_ps}


def init_stack_cache(cfg: ModelConfig, batch: int, seq_len: int, linear: bool = False) -> PyTree:
    unit, n_groups, tail = _unit_and_groups(cfg)
    groups = []
    for kind in unit:
        one = init_block_cache(cfg, kind, batch, seq_len, linear=linear)
        stacked = jax.tree.map(lambda x: jnp.broadcast_to(x, (n_groups,) + x.shape).copy(), one)
        groups.append(stacked)
    tail_cs = [
        init_block_cache(cfg, cfg.pattern[n_groups * len(unit) + i], batch, seq_len, linear=linear)
        for i in range(tail)
    ]
    return {"groups": tuple(groups), "tail": tail_cs}


def apply_stack(
    params: PyTree,
    x: jnp.ndarray,
    cfg: ModelConfig,
    positions: jnp.ndarray,
    caches: Optional[PyTree] = None,
    decode_pos=None,
    enc_kv_fn=None,
    moe_impl: str = "sort",
    seq_lens=None,
    slot_ids=None,
    page_tables=None,
    page_size: int = 0,
) -> Tuple[jnp.ndarray, Optional[PyTree], jnp.ndarray, jnp.ndarray]:
    """Apply all layers. enc_kv_fn(block_params, ) is handled by encdec path
    in model.py via per-block cross KV computed there (cross_kv passed as a
    stacked tensor through scan is handled by the caller precomputing KV).

    Returns (x, new_caches, aux_loss, expert_overflow) — overflow is the
    stack-total count of MoE routes dropped past capacity (always 0 for
    non-capacity moe_impl).
    """
    unit, n_groups, tail = _unit_and_groups(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    ovf_total = jnp.zeros((), jnp.int32)

    def group_body(carry, xs):
        x, aux, ovf = carry
        group_params, group_caches = xs
        if group_caches is None:
            x = constrain_activations(x, cfg)
        new_caches = []
        for j, kind in enumerate(unit):
            cache_j = None if group_caches is None else group_caches[j]
            x, nc, a, o = apply_block(
                group_params[j], x, cfg, kind, positions, cache_j,
                decode_pos=decode_pos, moe_impl=moe_impl, seq_lens=seq_lens,
                slot_ids=slot_ids, page_tables=page_tables, page_size=page_size,
            )
            new_caches.append(nc)
            aux = aux + a
            ovf = ovf + o
        out = tuple(new_caches) if group_caches is not None else None
        return (x, aux, ovf), out

    body = group_body
    if cfg.remat and caches is None:
        body = jax.checkpoint(group_body, prevent_cse=False)

    if n_groups > 0:
        xs = (params["groups"], caches["groups"] if caches is not None else None)
        if caches is None:
            # scan needs a concrete xs pytree: pair params only
            (x, aux_total, ovf_total), _ = jax.lax.scan(
                lambda c, gp: body(c, (gp, None)), (x, aux_total, ovf_total),
                params["groups"]
            )
            new_group_caches = None
        else:
            (x, aux_total, ovf_total), new_group_caches = jax.lax.scan(
                body, (x, aux_total, ovf_total), xs
            )
    else:
        new_group_caches = caches["groups"] if caches is not None else None

    new_tail = []
    for i, p in enumerate(params["tail"]):
        kind = cfg.pattern[n_groups * len(unit) + i]
        cache_i = None if caches is None else caches["tail"][i]

        def run(p_, x_, kind_=kind):
            return apply_block(
                p_, x_, cfg, kind_, positions, None, moe_impl=moe_impl
            )

        if cfg.remat and caches is None:
            x, _, a, o = jax.checkpoint(run, prevent_cse=False)(p, x)
            nc = None
        else:
            x, nc, a, o = apply_block(
                p, x, cfg, kind, positions, cache_i, decode_pos=decode_pos,
                moe_impl=moe_impl, seq_lens=seq_lens, slot_ids=slot_ids,
                page_tables=page_tables, page_size=page_size,
            )
        new_tail.append(nc)
        aux_total = aux_total + a
        ovf_total = ovf_total + o

    if caches is None:
        return x, None, aux_total, ovf_total
    return x, {"groups": new_group_caches, "tail": new_tail}, aux_total, ovf_total
