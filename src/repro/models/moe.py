"""Mixture-of-Experts layer with top-k token-choice routing.

Two dispatch implementations:

* ``sort`` (default) — sort-based capacity dispatch: the (token, k) choices
  are sorted by expert id and scattered into a fixed (E, C, d) buffer
  (C = capacity per expert).  Compute cost is E*C*d*ff ≈ top_k/E-active
  FLOPs times the capacity factor — the roofline-honest formulation.
  Overflowing tokens are dropped (their residual passes through), the
  standard capacity-based behaviour [GShard; Switch].
* ``dense`` — every expert runs on every token, combined by router probs.
  Exact (no capacity drops); used as the correctness oracle in tests and
  for tiny decode batches.

Expert weights carry an explicit leading expert axis that the sharding
rules map onto the mesh "model" axis => expert parallelism; the
scatter/gather around the expert compute is where the all-to-all lives.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import dense_init


def init_moe(rng, cfg: ModelConfig) -> Dict[str, jnp.ndarray]:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.expert_d_ff
    ks = jax.random.split(rng, 4)
    p = {
        "router": dense_init(ks[0], (d, e), dtype=cfg.params_dtype),
        "w_out": dense_init(ks[3], (e, f, d), in_axis=1, dtype=cfg.params_dtype),
    }
    if cfg.act in ("swiglu", "geglu"):
        p["w_gate"] = dense_init(ks[1], (e, d, f), in_axis=1, dtype=cfg.params_dtype)
        p["w_in"] = dense_init(ks[2], (e, d, f), in_axis=1, dtype=cfg.params_dtype)
    else:
        p["w_in"] = dense_init(ks[2], (e, d, f), in_axis=1, dtype=cfg.params_dtype)
    return p


def _router(p, x2d, cfg: ModelConfig):
    """x2d: (T, d). Returns (probs (T,k), ids (T,k), aux_loss)."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32), p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)  # renormalize (Mixtral-style)
    # Load-balancing aux loss (Switch): E * sum_e f_e * P_e
    e = cfg.n_experts
    counts = jnp.zeros((e,)).at[top_i.reshape(-1)].add(1.0)
    f_e = counts / jnp.maximum(counts.sum(), 1.0)
    p_e = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f_e * p_e)
    return top_p, top_i, aux


def _expert_ffn(p, xe, cfg: ModelConfig):
    """xe: (E, C, d) -> (E, C, d)."""
    cd = cfg.compute_dtype
    if cfg.act in ("swiglu", "geglu"):
        g = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"].astype(cd))
        u = jnp.einsum("ecd,edf->ecf", xe, p["w_in"].astype(cd))
        act = jax.nn.silu(g) if cfg.act == "swiglu" else jax.nn.gelu(g)
        h = act * u
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xe, p["w_in"].astype(cd)))
    return jnp.einsum("ecf,efd->ecd", h, p["w_out"].astype(cd))


_SEGMENT_TOKENS = 16384  # dispatch-buffer bound: ~seg*k/E*cf slots per expert


def apply_moe_sort(
    p, x: jnp.ndarray, cfg: ModelConfig, segment_tokens: int = _SEGMENT_TOKENS
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sort-based capacity dispatch. x: (B, S, d) -> (y, aux_loss).

    Tokens are processed in segments of ~``segment_tokens`` via ``lax.map``
    so the (E, C, d) dispatch buffer stays a bounded transient even when
    the SPMD partitioner replicates it (GShard-style grouping).
    """
    b, s, d = x.shape
    t_total = b * s
    n_seg = 1
    if t_total > segment_tokens:
        n_seg = t_total // segment_tokens
        while t_total % n_seg:
            n_seg -= 1
    if n_seg > 1:
        xs = x.reshape(n_seg, t_total // n_seg, 1, d)
        ys, auxs = jax.lax.map(lambda xi: _moe_sort_once(p, xi, cfg), xs)
        return ys.reshape(b, s, d), jnp.mean(auxs)
    y, aux = _moe_sort_once(p, x.reshape(t_total, 1, d), cfg)
    return y.reshape(b, s, d), aux


def _moe_sort_once(p, x, cfg: ModelConfig, psum_axis=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    cd = cfg.compute_dtype
    b, s, d = x.shape
    t = b * s
    k = cfg.top_k
    e = cfg.n_experts
    cap = max(int(t * k / e * cfg.capacity_factor), 1)

    x2d = x.reshape(t, d)
    top_p, top_i, aux = _router(p, x2d, cfg)

    flat_e = top_i.reshape(-1)  # (T*k,) expert id per choice
    flat_p = top_p.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t), k)  # token per choice

    order = jnp.argsort(flat_e)  # stable sort by expert
    se = flat_e[order]
    st = flat_t[order]
    sp = flat_p[order]

    # Position of each choice within its expert's segment.
    counts = jnp.zeros((e,), jnp.int32).at[se].add(1)
    starts = jnp.cumsum(counts) - counts  # exclusive prefix
    pos = jnp.arange(t * k, dtype=jnp.int32) - starts[se]
    valid = pos < cap

    slot = jnp.where(valid, se * cap + pos, e * cap)  # overflow -> scratch row
    buf = jnp.zeros((e * cap + 1, d), cd)
    buf = buf.at[slot].set(x2d[st].astype(cd), mode="drop")
    ye = _expert_ffn(p, buf[: e * cap].reshape(e, cap, d), cfg)
    if psum_axis is not None:
        # expert hidden dim is tensor-parallel inside shard_map: the w_out
        # contraction produced partial sums — reduce across the model axis.
        ye = jax.lax.psum(ye, psum_axis)

    out_choice = ye.reshape(e * cap, d)[jnp.minimum(slot, e * cap - 1)]
    out_choice = out_choice * (valid & (slot < e * cap))[:, None].astype(cd)
    y2d = jnp.zeros((t, d), cd).at[st].add(out_choice * sp[:, None].astype(cd))
    return y2d, aux


def apply_moe_capacity(
    p, x: jnp.ndarray, cfg: ModelConfig, valid: jnp.ndarray = None
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Serving-step capacity dispatch: (y, aux_loss, expert_overflow).

    The engine's MoE path.  Same sort-by-expert permutation as ``sort``,
    with the serving contract made explicit:

    * ``capacity = ceil(cf * tokens * top_k / n_experts)`` (static Python
      ceil, clamped to [1, tokens]; ``cf = inf`` means no drops — the
      dense-oracle parity point);
    * ``valid`` masks padding/idle tokens (dense chunked steps pass
      ``seq_lens``-derived masks, packed steps ``slot_ids >= 0``): invalid
      tokens are routed to a phantom expert bucket so they consume **no
      capacity** — a step's drops can't depend on how much padding the
      compiled shape carries;
    * ``expert_overflow`` counts real routed (token, choice) pairs dropped
      past capacity — per-expert DropCompute tau accounting, mirrored into
      ``StepStats.expert_overflow`` by the engine.

    Dropped choices fall through the residual path (the block adds y to
    x, so a fully-dropped token passes through unchanged), the standard
    capacity behaviour [GShard; Switch].

    At ``cf = inf`` the output is **byte-identical** to
    ``apply_moe_dense`` (the engine's parity criterion): the dense
    combine's zero-weight expert terms are exact FMA no-ops, so its
    accumulation reduces to the routed terms in expert-ascending order —
    reproduced here by sorting each token's k choices by expert index and
    combining with the same einsum contraction.
    """
    import math

    cd = cfg.compute_dtype
    b, s, d = x.shape
    t = b * s
    k = cfg.top_k
    e = cfg.n_experts
    cf = cfg.capacity_factor
    cap = t if math.isinf(cf) else min(max(math.ceil(t * k / e * cf), 1), t)

    x2d = x.reshape(t, d)
    top_p, top_i, aux = _router(p, x2d, cfg)
    valid_t = jnp.ones((t,), bool) if valid is None else valid.reshape(t)

    # invalid tokens route to phantom bucket e: they take no capacity
    flat_e = jnp.where(jnp.repeat(valid_t, k), top_i.reshape(-1), e)
    flat_p = top_p.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(flat_e)  # stable: within-expert keeps token order
    se, st, sp = flat_e[order], flat_t[order], flat_p[order]

    counts = jnp.zeros((e + 1,), jnp.int32).at[se].add(1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(t * k, dtype=jnp.int32) - starts[se]
    routed = se < e
    keep = routed & (pos < cap)
    overflow = jnp.sum(routed & ~keep)

    slot = jnp.where(keep, se * cap + pos, e * cap)
    buf = jnp.zeros((e * cap + 1, d), cd)
    buf = buf.at[slot].set(x2d[st].astype(cd), mode="drop")
    ye = _expert_ffn(p, buf[: e * cap].reshape(e, cap, d), cfg)

    # combine per token over its k choices, sorted ascending by expert —
    # the order (and einsum form) that bit-matches the dense oracle
    inv = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32)
    )
    slot_tk = slot[inv].reshape(t, k)
    keep_tk = keep[inv].reshape(t, k)
    out_tk = ye.reshape(e * cap, d)[jnp.minimum(slot_tk, e * cap - 1)]
    out_tk = out_tk * keep_tk[..., None].astype(cd)
    w_tk = jnp.where(keep_tk, top_p, 0.0).astype(cd)
    ksort = jnp.argsort(top_i, axis=1)
    y2d = jnp.einsum(
        "tk,tkd->td",
        jnp.take_along_axis(w_tk, ksort, axis=1),
        jnp.take_along_axis(out_tk, ksort[..., None], axis=1),
    )
    return y2d.reshape(b, s, d), aux, overflow


def apply_moe_dense(p, x: jnp.ndarray, cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Oracle: run all experts on all tokens, combine with router probs."""
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    top_p, top_i, aux = _router(p, x2d, cfg)
    # (T, E) combine weights
    comb = jnp.zeros((b * s, cfg.n_experts))
    comb = comb.at[jnp.arange(b * s)[:, None], top_i].add(top_p)
    ye = _expert_ffn(p, jnp.broadcast_to(x2d[None], (cfg.n_experts, b * s, d)), cfg)
    y2d = jnp.einsum("te,etd->td", comb.astype(cfg.compute_dtype), ye)
    return y2d.reshape(b, s, d), aux


def apply_moe_spmd(p, x: jnp.ndarray, cfg: ModelConfig, mesh) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Distribution-aware MoE: shard_map, local dispatch, TP experts.

    Under plain GSPMD the global argsort/scatter of the dispatch forces the
    partitioner to all-gather the token axis — activations become
    batch-replicated through the whole layer scan (observed: 16x residual
    blowup and TB-scale collectives).  Instead:

      * the data axes are mapped: every data shard dispatches its OWN
        tokens (local top-k, local sort, local capacity) — decentralized,
        no cross-worker coordination, exactly like DropCompute itself;
      * d_model stays sharded on the model axis through the dispatch (the
        (E, C, d) buffers scatter only the local d-slice — 16x smaller),
        w_in contracts the d-slice with one psum, w_out emits the local
        d-slice directly.  Works for any expert count, including
        mixtral's 8 experts on 16-way TP.
    """
    from jax.sharding import PartitionSpec as P

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    tp = "model" if "model" in mesh.axis_names else None
    while dp and x.shape[0] % _axes_size(mesh, dp) != 0:
        dp = dp[1:]
    dp_spec = dp if dp else None
    if tp is not None and x.shape[-1] % mesh.shape[tp] != 0:
        tp = None

    gated = cfg.act in ("swiglu", "geglu")
    # Two expert-TP factorizations — pick the one with less collective
    # volume (see EXPERIMENTS.md §Perf):
    #   d_psum (f < d, e.g. qwen3 f=1536): d-sharded contractions, psum the
    #     two (E,C,f) gate/up activations — volume ~ 2f per slot;
    #   ag_f  (f >= d, e.g. mixtral f=16384): all-gather the dispatched
    #     (E,C,d) tokens once, f-sharded experts (no gate/up psum), then
    #     reduce-scatter the (E,C,d) output — volume ~ 2d per slot.
    scheme = "ag_f" if cfg.expert_d_ff >= cfg.d_model else "d_psum"
    if scheme == "ag_f":
        w_specs = {
            "router": P(tp, None),
            "w_in": P(None, None, tp),
            "w_out": P(None, tp, None),
        }
        if gated:
            w_specs["w_gate"] = P(None, None, tp)
    else:
        w_specs = {
            "router": P(tp, None),
            "w_in": P(None, tp, None),
            "w_out": P(None, None, tp),
        }
        if gated:
            w_specs["w_gate"] = P(None, tp, None)

    def local_fn(p_local, xl):
        b, s, d = xl.shape
        y, aux = _moe_sort_local(p_local, xl.reshape(b * s, d), cfg, tp, scheme)
        if dp:
            aux = jax.lax.pmean(aux, dp)
        return y.reshape(b, s, d), aux

    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=({k: w_specs[k] for k in p}, P(dp_spec, None, tp)),
        out_specs=(P(dp_spec, None, tp), P()),
        check_vma=False,
    )
    return fn({k: p[k] for k in p}, x)


def _moe_sort_local(p, x2d, cfg: ModelConfig, tp, scheme: str = "d_psum"):
    """Per-device MoE body: local dispatch over the local d-slice.

    x2d: (T_local, d_local).  Router logits psum over tp (router weights
    are d-sharded); the dispatch scatters only the d-slice.  Expert TP per
    ``scheme``: "d_psum" contracts the d-slice with one psum per gate/up
    projection; "ag_f" all-gathers the dispatched slots to full d, runs
    f-sharded experts psum-free, and reduce-scatters the output back to
    the d-slice.
    """
    cd = cfg.compute_dtype
    t, d_local = x2d.shape
    k, e = cfg.top_k, cfg.n_experts
    cap = max(int(t * k / e * cfg.capacity_factor), 1)

    logits = jnp.einsum(
        "td,de->te", x2d.astype(jnp.float32), p["router"].astype(jnp.float32)
    )
    if tp is not None:
        logits = jax.lax.psum(logits, tp)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    counts = jnp.zeros((e,)).at[top_i.reshape(-1)].add(1.0)
    aux = e * jnp.sum((counts / jnp.maximum(counts.sum(), 1.0)) * jnp.mean(probs, axis=0))

    flat_e = top_i.reshape(-1)
    flat_p = top_p.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(flat_e)
    se, st, sp = flat_e[order], flat_t[order], flat_p[order]
    seg_counts = jnp.zeros((e,), jnp.int32).at[se].add(1)
    starts = jnp.cumsum(seg_counts) - seg_counts
    pos = jnp.arange(t * k, dtype=jnp.int32) - starts[se]
    valid = pos < cap
    slot = jnp.where(valid, se * cap + pos, e * cap)

    buf = jnp.zeros((e * cap + 1, d_local), cd)
    buf = buf.at[slot].set(x2d[st].astype(cd), mode="drop")
    xe = buf[: e * cap].reshape(e, cap, d_local)

    if scheme == "ag_f" and tp is not None:
        # gather dispatched slots to full d once; f-sharded experts need no
        # gate/up psum; reduce-scatter the output back to the d-slice.
        xe = jax.lax.all_gather(xe, tp, axis=-1, tiled=True)
        g = jnp.einsum("ecd,edf->ecf", xe, p["w_in"].astype(cd))
        if "w_gate" in p:
            u = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"].astype(cd))
            act = jax.nn.silu(u) if cfg.act == "swiglu" else jax.nn.gelu(u)
            h = act * g
        else:
            h = jax.nn.gelu(g)
        ye = jnp.einsum("ecf,efd->ecd", h, p["w_out"].astype(cd))
        ye = jax.lax.psum_scatter(ye, tp, scatter_dimension=2, tiled=True)
    else:
        # --- d-slice contractions with psum, f full, d-slice out ---
        g = jnp.einsum("ecd,edf->ecf", xe, p["w_in"].astype(cd))
        if tp is not None:
            g = jax.lax.psum(g, tp)
        if "w_gate" in p:
            u = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"].astype(cd))
            if tp is not None:
                u = jax.lax.psum(u, tp)
            act = jax.nn.silu(u) if cfg.act == "swiglu" else jax.nn.gelu(u)
            h = act * g
        else:
            h = jax.nn.gelu(g)
        ye = jnp.einsum("ecf,efd->ecd", h, p["w_out"].astype(cd))

    out_choice = ye.reshape(e * cap, d_local)[jnp.minimum(slot, e * cap - 1)]
    out_choice = out_choice * (valid & (slot < e * cap))[:, None].astype(cd)
    y2d = jnp.zeros((t, d_local), cd).at[st].add(out_choice * sp[:, None].astype(cd))
    return y2d, aux


def _axes_size(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape.get(a, 1)
    return n


def apply_moe(p, x, cfg: ModelConfig, impl: str = "sort", mesh=None):
    if impl == "dense":
        return apply_moe_dense(p, x, cfg)
    if impl == "spmd":
        if mesh is None:
            mesh = _current_mesh()
        if mesh is not None and mesh.size > 1:
            return apply_moe_spmd(p, x, cfg, mesh)
        return apply_moe_sort(p, x, cfg)
    return apply_moe_sort(p, x, cfg)


def _current_mesh():
    """The mesh set by an enclosing ``jax.set_mesh`` (as an
    ``AbstractMesh`` while tracing), or None outside one."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m
