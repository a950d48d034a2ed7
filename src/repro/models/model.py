"""Public model API: init / forward / loss / decode, for every family.

    params = init_params(rng, cfg)
    logits, aux = forward(params, cfg, batch)           # train / prefill
    loss_sum, w = loss_fn(params, cfg, batch)           # DropCompute GradFn
    cache = init_decode_cache(params, cfg, batch, L)    # serving
    logits, cache = decode_step(params, cfg, cache, tok, pos)

``batch`` is a dict with (family-dependent):
    tokens   (B, S) int32          — always
    weights  (B, S) float          — per-token loss weights (0 = pad/prefix)
    prefix   (B, P, d) bf16        — VLM patch embeddings (stub frontend)
    frames   (B, F, d) bf16        — audio encoder frames (stub frontend)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from . import layers as L
from .transformer import (
    apply_block,
    apply_stack,
    init_block,
    init_block_cache,
    init_stack,
    init_stack_cache,
)

PyTree = Any


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_params(rng, cfg: ModelConfig) -> PyTree:
    cfg.validate()
    ks = jax.random.split(rng, 8)
    p: Dict[str, PyTree] = {
        "embed": L.init_embedding(ks[0], cfg),
        "stack": init_stack(ks[1], cfg),
        "final_norm": L.init_norm(cfg),
    }
    if cfg.is_encdec:
        p["encoder"] = {
            "blocks": [init_block(jax.random.fold_in(ks[2], i), cfg, "B") for i in range(cfg.enc_layers)],
            "final_norm": L.init_norm(cfg),
            "pos_embedding": L.dense_init(ks[3], (cfg.enc_seq, cfg.d_model), in_axis=1, dtype=cfg.params_dtype),
        }
        # decoder cross-attention blocks replace the plain stack
        p["stack"] = {
            "groups": (),
            "tail": [
                init_block(jax.random.fold_in(ks[4], i), cfg, "G", cross=True)
                for i in range(cfg.n_layers)
            ],
        }
    return p


# ---------------------------------------------------------------------------
# Encoder (audio; the conv/mel frontend is a stub per the assignment)
# ---------------------------------------------------------------------------


def encode(params: PyTree, cfg: ModelConfig, frames: jnp.ndarray) -> jnp.ndarray:
    """frames: (B, F, d) stub embeddings -> encoder output (B, F, d)."""
    enc = params["encoder"]
    x = frames.astype(cfg.compute_dtype)
    x = x + enc["pos_embedding"][None, : x.shape[1]].astype(cfg.compute_dtype)
    positions = jnp.arange(x.shape[1])

    def run(blk_, x_):
        y, _, _, _ = apply_block(blk_, x_, cfg, "B", positions)
        return y

    for blk in enc["blocks"]:
        x = jax.checkpoint(run, prevent_cse=False)(blk, x) if cfg.remat else run(blk, x)
    return L.apply_norm(enc["final_norm"], x, cfg)


def _cross_kv(blk, enc_out, cfg: ModelConfig):
    cd = cfg.compute_dtype
    k = jnp.einsum("bsd,dhk->bshk", enc_out, blk["cross_attn"]["wk"].astype(cd))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, blk["cross_attn"]["wv"].astype(cd))
    return k, v


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def forward_features(
    params: PyTree,
    cfg: ModelConfig,
    batch: Dict[str, jnp.ndarray],
    moe_impl: str = "sort",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (final hidden states (B, S_text, d), aux_loss scalar)."""
    tokens = batch["tokens"]
    positions = jnp.arange(tokens.shape[1])

    if cfg.prefix_len > 0:  # VLM: prepend patch embeddings
        prefix = batch["prefix"].astype(cfg.compute_dtype)
        x_text = L.embed(params["embed"], tokens, cfg, positions)
        x = jnp.concatenate([prefix, x_text], axis=1)
        positions = jnp.arange(x.shape[1])
    else:
        x = L.embed(params["embed"], tokens, cfg, positions)

    if cfg.is_encdec:
        enc_out = encode(params, cfg, batch["frames"])
        aux = jnp.zeros((), jnp.float32)

        def run(blk_, x_, enc_):
            y, _, a_, _ = apply_block(
                blk_, x_, cfg, "G", positions, enc_kv=_cross_kv(blk_, enc_, cfg)
            )
            return y, a_

        for blk in params["stack"]["tail"]:
            if cfg.remat:
                x, a = jax.checkpoint(run, prevent_cse=False)(blk, x, enc_out)
            else:
                x, a = run(blk, x, enc_out)
            aux = aux + a
    else:
        x, _, aux, _ = apply_stack(params["stack"], x, cfg, positions, moe_impl=moe_impl)

    x = L.apply_norm(params["final_norm"], x, cfg)
    if cfg.prefix_len > 0:
        x = x[:, cfg.prefix_len :]
    return x, aux


def forward(
    params: PyTree,
    cfg: ModelConfig,
    batch: Dict[str, jnp.ndarray],
    moe_impl: str = "sort",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (logits (B, S, V), aux_loss scalar)."""
    x, aux = forward_features(params, cfg, batch, moe_impl=moe_impl)
    logits = L.unembed(params["embed"], x, cfg)
    return logits, aux


# ---------------------------------------------------------------------------
# Loss (sum form, for DropCompute's accumulate_grads)
# ---------------------------------------------------------------------------

_CE_CHUNK = 1024  # sequence positions per unembed+CE chunk


def _ce_sums(params, cfg, x, targets, w):
    """(loss_sum, weight_sum) from final hiddens; chunked over sequence.

    The unembed logits (B, S, V) in fp32 dominate training memory at large
    vocabs (several full copies live through the CE backward).  Chunking
    the positions through a checkpointed map keeps logits transient.
    """
    b, s, d = x.shape
    if s <= _CE_CHUNK:
        return _ce_once(params, cfg, x, targets, w)

    pad = (-s) % _CE_CHUNK
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        w = jnp.pad(w, ((0, 0), (0, pad)))
    n = (s + pad) // _CE_CHUNK
    xc = jnp.moveaxis(x.reshape(b, n, _CE_CHUNK, d), 1, 0)
    tc = jnp.moveaxis(targets.reshape(b, n, _CE_CHUNK), 1, 0)
    wc = jnp.moveaxis(w.reshape(b, n, _CE_CHUNK), 1, 0)

    def one(args):
        return _ce_once(params, cfg, *args)

    sums = jax.lax.map(jax.checkpoint(one), (xc, tc, wc))
    return jnp.sum(sums[0]), jnp.sum(sums[1])


def _ce_once(params, cfg, x, targets, w):
    logits = L.unembed(params["embed"], x, cfg).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum((lse - tgt) * w), jnp.sum(w)


def loss_fn(
    params: PyTree,
    cfg: ModelConfig,
    batch: Dict[str, jnp.ndarray],
    moe_impl: str = "sort",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Next-token CE. Returns (loss_sum, token_weight_sum)."""
    x, aux = forward_features(params, cfg, batch, moe_impl=moe_impl)
    targets = batch["tokens"][:, 1:]
    w = batch.get("weights")
    w = jnp.ones_like(targets, jnp.float32) if w is None else w[:, 1:].astype(jnp.float32)
    loss_sum, w_sum = _ce_sums(params, cfg, x[:, :-1], targets, w)
    loss_sum = loss_sum + cfg.router_aux_weight * aux * w_sum
    return loss_sum, w_sum


def per_token_losses(params, cfg, batch, moe_impl: str = "sort"):
    """(B, S-1) CE and weights — for the per-example-weight SPMD step."""
    logits, aux = forward(params, cfg, batch, moe_impl=moe_impl)
    targets = batch["tokens"][:, 1:]
    w = batch.get("weights")
    w = jnp.ones_like(targets, jnp.float32) if w is None else w[:, 1:].astype(jnp.float32)
    lg = logits[:, :-1].astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return lse - tgt, w, aux


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


class UnsupportedPatternError(NotImplementedError):
    """A serving path was asked for a layer pattern it cannot run.

    Typed (and raised unconditionally, not ``assert``-ed — asserts vanish
    under ``python -O``) so callers can catch it and fall back to
    ``decode_step`` token streaming for recurrent/SSM models.
    """


def require_chunkable(cfg: ModelConfig, what: str = "chunked prefill") -> None:
    """Raise ``UnsupportedPatternError`` unless ``cfg`` supports multi-token
    serving steps (decoder-only; any mix of 'G'/'L'/'R'/'M' layers —
    recurrent state is advanced by the chunk/segment scan, attention KV by
    multi-row cache writes).  Enc-dec models stay decode_step-only."""
    if not set(cfg.pattern) <= {"G", "L", "R", "M"}:
        raise UnsupportedPatternError(
            f"{what} supports 'G'/'L'/'R'/'M' layer patterns, got "
            f"{cfg.pattern!r}"
        )
    if cfg.is_encdec:
        raise UnsupportedPatternError(f"{what} does not support enc-dec models")


def _cache_parts(cache):
    """Split a decode cache into (data, page_tables, page_size).

    Every serving path accepts either the legacy cache dict (dense slots)
    or a ``repro.serve.kv.KVState`` — duck-typed on its ``data`` attribute
    so ``models`` never imports ``serve``.  For a dense ``KVState`` (or a
    plain dict) the tables are ``None`` and the model paths behave exactly
    as before; for a paged one, every scatter/gather translates
    ``(slot, position)`` through the block tables.
    """
    data = getattr(cache, "data", cache)
    return data, getattr(cache, "tables", None), getattr(cache, "page_size", 0)


def _cache_rebuild(cache, new_data):
    """Rewrap updated cache data in the caller's container type."""
    if hasattr(cache, "data"):
        return dataclasses.replace(cache, data=new_data)
    return new_data


def init_decode_cache(
    params: PyTree,
    cfg: ModelConfig,
    batch: int,
    seq_len: int,
    enc_out: Optional[jnp.ndarray] = None,
    linear: bool = False,
) -> PyTree:
    """Pre-allocated KV/state cache for ``decode_step`` / ``prefill_chunk``.

    linear=True allocates full-length (non-ring) buffers for sliding-window
    layers; required by ``prefill_chunk`` (the serving engine), whose
    multi-token scatter writes assume absolute positions never wrap.

    This builds the dense-slot layout; serving callers that want paged KV
    (or the cache API in general) go through ``repro.serve.kv.KVCacheSpec``
    — every decode path here accepts its ``KVState`` in place of the dict.
    """
    if cfg.is_encdec:
        # the enc-dec decoder stack is tail-only (see init_params): its
        # cache must mirror that structure, not the grouped-scan layout
        if enc_out is None:  # typed, not assert: must survive python -O
            raise ValueError("enc-dec decode needs encoder output (enc_out)")
        cache: Dict[str, PyTree] = {
            "stack": {
                "groups": (),
                "tail": [
                    init_block_cache(cfg, "G", batch, seq_len)
                    for _ in range(cfg.n_layers)
                ],
            },
            "cross_kv": [
                _cross_kv(blk, enc_out, cfg) for blk in params["stack"]["tail"]
            ],
        }
        return cache
    return {"stack": init_stack_cache(cfg, batch, seq_len, linear=linear)}


def prefill_chunk(
    params: PyTree,
    cfg: ModelConfig,
    cache: PyTree,
    tokens: jnp.ndarray,  # (B, C) int32
    pos: jnp.ndarray,  # (B,) first absolute position per slot
    seq_lens: jnp.ndarray,  # (B,) active token count per slot (0 = idle)
    moe_impl: str = "dense",
    return_aux: bool = False,
) -> Tuple[jnp.ndarray, PyTree]:
    """Process up to C prompt tokens per slot in one step (chunked prefill).

    Slot i consumes ``tokens[i, :seq_lens[i]]`` at absolute positions
    ``pos[i]..pos[i]+seq_lens[i]-1``, writing its KV-cache rows there;
    padding columns neither write the cache nor produce meaningful logits.
    Returns logits for every (slot, column) — the caller reads column
    ``seq_lens[i]-1`` when slot i just finished its prompt, or column 0
    for a single-token decode slot.  With C == 1 and seq_lens in {0, 1}
    this is a decode step that skips idle slots, so one function serves
    the whole mixed decode+prefill engine iteration.

    The cache must be allocated with ``init_decode_cache(..., linear=True)``
    (no ring buffers).  Recurrent layers ('R'/'M') run a chunk scan seeded
    from (and writing back to) their per-slot carried state — columns past
    a row's ``seq_lens`` are an exact state identity, so idle slots keep
    their state bit-for-bit.

    ``return_aux=True`` (static) additionally returns a per-step stats
    dict — currently ``{"expert_overflow"}``, the count of MoE routes
    dropped past expert capacity this step (0 unless
    ``moe_impl="capacity"``).

    Host-side driver loops synchronize each step (e.g.
    ``jax.block_until_ready`` or materializing the sampled token) before
    reusing the host-side token/position buffers: with async dispatch a
    CPU backend was seen reading them after they were rebound.
    ``ContinuousBatcher`` does this for you.

    ``cache`` is the legacy dict from ``init_decode_cache`` or a
    ``repro.serve.kv.KVState`` (dense or paged); the returned cache has
    the same container type as the input.
    """
    require_chunkable(cfg, "chunked prefill")
    data, tables, page_size = _cache_parts(cache)
    pos = jnp.asarray(pos)
    c = tokens.shape[1]
    positions = pos[:, None] + jnp.arange(c)[None, :]  # (B, C) for RoPE
    x = L.embed(params["embed"], tokens, cfg, positions)
    x, new_stack, _, ovf = apply_stack(
        params["stack"], x, cfg, positions, data["stack"],
        decode_pos=pos, seq_lens=jnp.asarray(seq_lens), moe_impl=moe_impl,
        page_tables=tables, page_size=page_size,
    )
    x = L.apply_norm(params["final_norm"], x, cfg)
    logits = L.unembed(params["embed"], x, cfg)
    out_cache = _cache_rebuild(cache, {"stack": new_stack})
    if return_aux:
        return logits, out_cache, {"expert_overflow": ovf}
    return logits, out_cache


def verify_step(
    params: PyTree,
    cfg: ModelConfig,
    cache: PyTree,
    tokens: jnp.ndarray,  # (B, 1 + k) int32: [last emitted, draft_1..draft_k]
    pos: jnp.ndarray,  # (B,) first absolute position per slot
    seq_lens: jnp.ndarray,  # (B,) 1 + drafts granted per slot (0 = idle)
    moe_impl: str = "dense",
) -> Tuple[jnp.ndarray, PyTree]:
    """Speculative-decoding verify path: score ``k`` draft tokens per slot
    in one bounded step, returning **per-position** logits.

    Row i carries ``[t_last, d_1, .., d_k]`` at the slot's absolute
    positions; column ``j`` of the returned ``(B, 1 + k, V)`` logits is
    the model's **full** next-token distribution after consuming the row
    through column ``j`` — per-column probabilities, not a pre-reduced
    argmax, which is what both acceptance rules need: greedy acceptance
    keeps the longest prefix where ``d_{j+1} == argmax(logits[:, j])``,
    and rejection-sampling acceptance (``serve.spec.accept_sampled``)
    samples each column with the request's own params and per-position
    PRNG key (``serve.sampling.sample_tokens``) and keeps the prefix the
    samples confirm — the first mismatching column supplies the
    bonus/resampled token either way.  This *is* ``prefill_chunk``:
    verification
    is chunked prefill at the slot's absolute positions (the same
    shape-stable compiled program family as mixed prefill+decode steps),
    which means the drafts' KV lands in the cache as a side effect and
    the accepted prefix needs no recompute.  Rejected positions are the
    caller's rollback: a position-mask trim for dense slots (stale rows
    are never attended) or ``KVCache.trim_slot`` for the paged layout.

    The serving engine's jitted step IS this program (one compiled step
    serves prefill, decode, and verify grants alike); this named entry
    point is the contract for direct callers and is pinned against a
    sequential ``decode_step`` loop in ``tests/test_serve_spec.py``.
    """
    return prefill_chunk(params, cfg, cache, tokens, pos, seq_lens, moe_impl=moe_impl)


def packed_prefill(
    params: PyTree,
    cfg: ModelConfig,
    cache: PyTree,
    tokens: jnp.ndarray,  # (P,) int32 packed granted tokens
    slot_ids: jnp.ndarray,  # (P,) int32 cache slot per token (< 0 = padding)
    positions: jnp.ndarray,  # (P,) int32 absolute cache position per token
    moe_impl: str = "dense",
    return_aux: bool = False,
) -> Tuple[jnp.ndarray, PyTree]:
    """Token-packed engine step: granted tokens alone determine compute.

    The dense ``prefill_chunk`` computes the full (B, C) shape however few
    tokens the scheduler granted; this path takes the flattened layout
    from ``repro.serve.packing`` — one row per granted token, P fixed at
    the engine's packed capacity — and runs the whole mixed decode+prefill
    iteration as a single (1, P) batch.  Each token writes its K/V into
    ``cache`` at (slot_ids[j], positions[j]) and attends only within its
    own slot (segment-aware masking via the per-token slot gather; see
    ``apply_attention``), so requests packed side by side can never leak
    into each other.  Returns logits (P, V); the caller reads each slot's
    final granted row.  Same cache contract as ``prefill_chunk``:
    ``init_decode_cache(..., linear=True)`` — or a paged
    ``repro.serve.kv.KVState``, whose block tables route every
    ``(slot, position)`` to its physical page row.  Recurrent layers
    ('R'/'M') run a segment-masked scan over the packed axis: each
    segment injects its slot's carried state at its first token and the
    last token writes the state back (``models/recurrent.py``); the
    pack_step invariant that a slot's tokens are contiguous is what makes
    one global scan per step sound.  ``return_aux`` as in
    ``prefill_chunk``.
    """
    require_chunkable(cfg, "packed prefill")
    data, tables, page_size = _cache_parts(cache)
    tokens = jnp.asarray(tokens)[None]  # (1, P)
    pos2 = jnp.asarray(positions)[None]  # (1, P)
    x = L.embed(params["embed"], tokens, cfg, pos2)
    x, new_stack, _, ovf = apply_stack(
        params["stack"], x, cfg, pos2, data["stack"],
        slot_ids=jnp.asarray(slot_ids), moe_impl=moe_impl,
        page_tables=tables, page_size=page_size,
    )
    x = L.apply_norm(params["final_norm"], x, cfg)
    logits = L.unembed(params["embed"], x, cfg)
    out_cache = _cache_rebuild(cache, {"stack": new_stack})
    if return_aux:
        return logits[0], out_cache, {"expert_overflow": ovf}
    return logits[0], out_cache


def decode_step(
    params: PyTree,
    cfg: ModelConfig,
    cache: PyTree,
    token: jnp.ndarray,  # (B, 1) int32
    pos: jnp.ndarray,  # scalar int32, or (B,) per-slot positions
    moe_impl: str = "dense",
) -> Tuple[jnp.ndarray, PyTree]:
    data, tables, page_size = _cache_parts(cache)
    pos = jnp.asarray(pos)
    positions = pos[:, None] if pos.ndim else jnp.full((1,), pos, jnp.int32)
    x = L.embed(params["embed"], token, cfg, positions)

    if tables is not None and cfg.is_encdec:
        raise UnsupportedPatternError("paged KV does not support enc-dec models")
    if cfg.is_encdec:
        new_tail = []
        for blk, c, kv in zip(
            params["stack"]["tail"], data["stack"]["tail"], data["cross_kv"]
        ):
            x, nc, _, _ = apply_block(
                blk, x, cfg, "G", positions, c, decode_pos=pos, enc_kv=kv
            )
            new_tail.append(nc)
        new_data = {
            "stack": {"groups": data["stack"]["groups"], "tail": new_tail},
            "cross_kv": data["cross_kv"],
        }
    else:
        x, new_stack, _, _ = apply_stack(
            params["stack"], x, cfg, positions, data["stack"],
            decode_pos=pos, moe_impl=moe_impl,
            page_tables=tables, page_size=page_size,
        )
        new_data = {"stack": new_stack}

    x = L.apply_norm(params["final_norm"], x, cfg)
    logits = L.unembed(params["embed"], x, cfg)
    return logits, _cache_rebuild(cache, new_data)
