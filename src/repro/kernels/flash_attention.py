"""Flash attention Pallas TPU kernel (causal / sliding-window, GQA).

TPU-native blocking: queries are tiled to (BLOCK_Q, head_dim) VMEM tiles
and the kernel streams key/value tiles of (BLOCK_K, head_dim) through
VMEM, maintaining the online-softmax running max/sum in VREGs.  Block
sizes default to 128 to align with the MXU's 128x128 systolic array and
the (8, 128) VREG lanes.

Grid: (batch*kv_heads*q_groups, Sq / BLOCK_Q).  Each program instance owns
one query tile for one (batch, head) pair and loops over its admissible
key tiles with ``jax.lax.fori_loop`` (causal/sliding-window pruning of the
loop bounds — skipped tiles cost nothing, the TPU analogue of the CUDA
early-exit).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _attn_kernel(
    q_ref, k_ref, v_ref, *rest,
    sq, sk, block_q, block_k, causal, window, sm_scale, segmented=False,
):
    if segmented:
        q_seg_ref, kv_seg_ref, o_ref = rest
    else:
        (o_ref,) = rest
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * sm_scale  # (block_q, d)

    q_start = qi * block_q
    qpos = q_start + jax.lax.iota(jnp.int32, block_q) + (sk - sq)  # right-aligned

    # Admissible key-tile range for this query tile (loop-bound pruning).
    # Segment masking composes with, but never widens, these bounds: a
    # key tile skipped by causality can hold no same-segment admissible
    # key either (segments are position-contiguous by construction).
    if causal:
        hi = jnp.minimum((q_start + block_q - 1 + (sk - sq)) // block_k + 1, sk // block_k)
    else:
        hi = sk // block_k
    if window > 0:
        lo = jnp.maximum((q_start + (sk - sq) - window + 1) // block_k, 0)
    else:
        lo = 0

    if segmented:
        qseg = q_seg_ref[...]  # (block_q, 1): a column, keys' ids are a row

    def body(ki, carry):
        acc, m_prev, l_prev = carry
        start = pl.multiple_of(ki * block_k, block_k)
        k_tile = k_ref[pl.ds(start, block_k), :]
        v_tile = v_ref[pl.ds(start, block_k), :]
        s = jnp.dot(q, k_tile.astype(jnp.float32).T)  # (bq, bk)

        kpos = ki * block_k + jax.lax.iota(jnp.int32, block_k)
        mask = jnp.ones((block_q, block_k), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window > 0:
            mask &= kpos[None, :] > qpos[:, None] - window
        if segmented:
            kseg = kv_seg_ref[:, pl.ds(start, block_k)]  # (1, block_k)
            mask &= qseg == kseg
        s = jnp.where(mask, s, NEG_INF)

        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_cur = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + jnp.dot(p, v_tile.astype(jnp.float32))
        return acc, m_cur, l_cur

    d = q_ref.shape[-1]
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(lo, hi, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,  # (B, H, Sq, D)
    k: jnp.ndarray,  # (B, KV, Sk, D)
    v: jnp.ndarray,  # (B, KV, Sk, D)
    causal: bool = True,
    window: int = 0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
    q_segment_ids: jnp.ndarray = None,  # (B, Sq) int32
    kv_segment_ids: jnp.ndarray = None,  # (B, Sk) int32
) -> jnp.ndarray:
    """Flash attention; optional segment masking for token-packed batches.

    With segment ids, query i additionally requires
    ``q_segment_ids[b, i] == kv_segment_ids[b, j]`` to attend key j — the
    mask term that keeps requests flattened side by side into one packed
    sequence from attending across their boundaries.  Segments must be
    position-contiguous (the packed layout guarantees this) so the
    causal/window loop-bound pruning stays valid; a query with no
    admissible key returns the mean of its visited value tiles (callers
    mask such padding rows out).
    """
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    g = h // kvh
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    segmented = q_segment_ids is not None
    if segmented != (kv_segment_ids is not None):
        # raised, not assert-ed: under python -O a half-passed pair would
        # silently disable the mask — a cross-request KV leak
        raise ValueError("pass both q_segment_ids and kv_segment_ids, or neither")

    # Flatten (B, KV, G) onto the leading grid axis; queries grouped by KV.
    qr = q.reshape(b * kvh * g, sq, d)
    kr = jnp.repeat(k.reshape(b * kvh, sk, d), g, axis=0)
    vr = jnp.repeat(v.reshape(b * kvh, sk, d), g, axis=0)

    kernel = functools.partial(
        _attn_kernel,
        sq=sq, sk=sk, block_q=block_q, block_k=block_k,
        causal=causal, window=window, sm_scale=1.0 / math.sqrt(d),
        segmented=segmented,
    )
    in_specs = [
        pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((None, sk, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((None, sk, d), lambda i, j: (i, 0, 0)),
    ]
    operands = [qr, kr, vr]
    if segmented:
        # Segment ids are per (batch, position): grid axis 0 runs over
        # b*h flattened programs, so the index map recovers the batch.
        # Query ids come as a (Sq, 1) column and key ids as a (1, Sk) row
        # per batch row, so each block's last two dimensions are legal
        # TPU tiles and the mask needs no in-kernel transpose.
        in_specs.append(pl.BlockSpec((None, block_q, 1), lambda i, j: (i // h, j, 0)))
        in_specs.append(pl.BlockSpec((None, 1, sk), lambda i, j: (i // h, 0, 0)))
        operands.append(q_segment_ids.astype(jnp.int32)[:, :, None])
        operands.append(kv_segment_ids.astype(jnp.int32)[:, None, :])
    out = pl.pallas_call(
        kernel,
        grid=(b * h, sq // block_q),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        interpret=interpret,
    )(*operands)
    return out.reshape(b, h, sq, d)


# ---------------------------------------------------------------------------
# Paged variant: gather-by-block-table (the repro.serve.kv Paged layout)
# ---------------------------------------------------------------------------


def _paged_attn_kernel(
    tbl_ref, pos_ref, slot_ref, q_ref, k_ref, v_ref, *rest,
    page_size, num_pages, num_blocks, window, sm_scale, softcap, quantized,
):
    if quantized:
        ks_ref, vs_ref, o_ref = rest
    else:
        (o_ref,) = rest
    # scalar-prefetched (SMEM) addressing for this program's query token
    t = pl.program_id(1)
    pos = pos_ref[t]
    slot = slot_ref[t]
    valid_q = slot >= 0
    slot_s = jnp.maximum(slot, 0)
    q = q_ref[...].astype(jnp.float32) * sm_scale  # (G, D): the KV head's group

    # Admissible logical-block range for this query: its slot's blocks up
    # to (and including) its own position's block, lower-bounded by the
    # sliding window.  Skipped blocks cost nothing — decode reads exactly
    # ceil((pos+1)/page_size) pages, not the whole pool.
    hi = jnp.where(valid_q, jnp.minimum(pos // page_size + 1, num_blocks), 0)
    if window > 0:
        lo = jnp.maximum((pos - window + 1) // page_size, 0)
    else:
        lo = 0

    def body(bi, carry):
        acc, m_prev, l_prev = carry
        page = tbl_ref[slot_s * num_blocks + bi]
        # unallocated sentinel (num_pages) AND hostile negatives: a bad
        # table entry may only redirect the read to a masked tile, never
        # wrap around into another slot's pages
        ok = (page >= 0) & (page < num_pages)
        page_s = jnp.clip(page, 0, num_pages - 1)
        start = pl.multiple_of(page_s * page_size, page_size)
        k_tile = k_ref[pl.ds(start, page_size), :].astype(jnp.float32)  # (ps, d)
        v_tile = v_ref[pl.ds(start, page_size), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_tile, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (G, ps)
        if quantized:
            # int8 pages: per-row dequant scales (per (page-row, kv-head),
            # written at quantization time alongside the int8 rows) applied
            # to the scores and to the softmax weights — the same sums as
            # scaling the K/V rows, without the (ps, d) multiplies
            s = s * ks_ref[pl.ds(page_s, 1), :]
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        kpos = bi * page_size + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
        mask = (kpos <= pos) & ok
        if window > 0:
            mask &= kpos > pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        # mask p explicitly: when every position so far is masked, m_cur
        # is still NEG_INF and exp(s - m_cur) would be 1, not 0 — a
        # fully-masked query must come out all-zero
        p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
        l_cur = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if quantized:
            p = p * vs_ref[pl.ds(page_s, 1), :]
        acc = acc * alpha + jnp.dot(p, v_tile, preferred_element_type=jnp.float32)
        return acc, m_cur, l_cur

    g, d = q.shape
    acc0 = jnp.zeros((g, d), jnp.float32)
    m0 = jnp.full((g, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((g, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(lo, hi, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_flash_attention(
    q: jnp.ndarray,  # (T, H, D) packed query tokens
    k_pool: jnp.ndarray,  # (num_pages, page_size, KV, D)
    v_pool: jnp.ndarray,  # (num_pages, page_size, KV, D)
    tables: jnp.ndarray,  # (num_slots, num_blocks) int32
    q_pos: jnp.ndarray,  # (T,) absolute position per query token
    q_slots: jnp.ndarray,  # (T,) cache slot per query token; < 0 = padding
    window: int = 0,
    softcap: float = 0.0,
    k_scale: jnp.ndarray = None,  # (num_pages, page_size, KV) f32, int8 pools
    v_scale: jnp.ndarray = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Flash attention over a paged KV pool (vLLM-style paged attention).

    The serving engine's token-packed decode/prefill step addresses KV by
    ``(slot, position)``; under the ``repro.serve.kv`` Paged layout the
    physical row is ``(tables[slot, position // page_size], position %
    page_size)``.  Instead of materializing each token's logical buffer
    (what the jnp path does), the kernel walks the query's *block table*:
    one program per (KV head, token) runs the online-softmax loop for the
    head's whole query group over that slot's admissible logical blocks
    only, loading each key tile by its page id — the loop never reads
    another slot's pages, so cross-request isolation is structural, not a
    mask (adversarially tested in ``tests/test_kernels.py``).  Entries
    with ``tables[s, b] >= num_pages`` (the unallocated sentinel) or
    ``< 0`` are mask-dropped; padding queries (``q_slots < 0``) return
    zero rows.

    TPU blocking: the block tables, positions and slots are scalar
    prefetch (SMEM), so each program reads its addressing as scalars;
    queries are blocked ``(G, D)`` per (KV head, token) — whole last two
    dimensions, as the TPU compiler requires.  The grid runs KV heads
    outermost, so a head's pool block stays resident while every token
    attends to it.

    Like the dense kernel above (whole-K block specs), each program
    *stages* the full per-head pool as one VMEM block and prunes reads
    inside it, which bounds the pool by the compiler's scoped VMEM limit
    (its default, not the chip's whole VMEM).  At internlm2-1.8b widths
    (8 KV heads, head_dim 128, 16-token pages, 64 query tokens) the
    largest pool that compiles for a v5e is 1,792 pages (28,672 tokens)
    with bf16 pages and 3,184 pages (50,944 tokens) with int8 pages; 8
    pages more and the compiler runs out of VMEM.  Lifting that bound
    needs a kernel that DMAs pages by table entry from an HBM-resident
    pool.

    The jnp oracle is ``repro.kernels.ref.paged_attention_ref``; the
    fused XLA path used off-TPU is ``paged_attention_xla`` below.
    """
    t, h, d = q.shape
    num_pages, page_size, kvh, _ = k_pool.shape
    num_slots, num_blocks = tables.shape
    g = h // kvh
    quantized = k_scale is not None
    if quantized != (v_scale is not None):
        # raised, not assert-ed: a half-passed pair would silently attend
        # over raw int8 codes for one of K/V
        raise ValueError("pass both k_scale and v_scale, or neither")

    # (KV, num_pages * page_size, D): one flat row pool per KV head, so a
    # page id turns into a row-slice start inside the kernel.
    rows = num_pages * page_size
    kr = k_pool.transpose(2, 0, 1, 3).reshape(kvh, rows, d)
    vr = v_pool.transpose(2, 0, 1, 3).reshape(kvh, rows, d)
    qr = q.reshape(t, kvh, g, d)

    kernel = functools.partial(
        _paged_attn_kernel,
        page_size=page_size, num_pages=num_pages, num_blocks=num_blocks,
        window=window, sm_scale=1.0 / math.sqrt(d), softcap=softcap,
        quantized=quantized,
    )
    # index maps take (grid indices..., *scalar prefetch refs)
    in_specs = [
        pl.BlockSpec((None, None, g, d), lambda j, i, *_: (i, j, 0, 0)),
        pl.BlockSpec((None, rows, d), lambda j, i, *_: (j, 0, 0)),
        pl.BlockSpec((None, rows, d), lambda j, i, *_: (j, 0, 0)),
    ]
    operands = [qr, kr, vr]
    if quantized:
        # Per-row dequant scales, one (num_pages, page_size) plane per KV
        # head: a page id is a row of it.
        for sc in (k_scale, v_scale):
            in_specs.append(
                pl.BlockSpec((None, num_pages, page_size), lambda j, i, *_: (j, 0, 0))
            )
            operands.append(sc.transpose(2, 0, 1).astype(jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(kvh, t),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, None, g, d), lambda j, i, *_: (i, j, 0, 0)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, kvh, g, d), q.dtype),
        interpret=interpret,
    )(
        tables.astype(jnp.int32).reshape(num_slots * num_blocks),
        q_pos.astype(jnp.int32),
        q_slots.astype(jnp.int32),
        *operands,
    )
    return out.reshape(t, h, d)


def paged_attention_xla(
    q: jnp.ndarray,  # (T, H, D) packed query tokens
    k_pool: jnp.ndarray,  # (num_pages, page_size, KV, D)
    v_pool: jnp.ndarray,  # (num_pages, page_size, KV, D)
    tables: jnp.ndarray,  # (num_slots, num_blocks) int32
    q_pos: jnp.ndarray,  # (T,) absolute positions
    q_slots: jnp.ndarray,  # (T,) slot per query; < 0 = padding
    window: int = 0,
    softcap: float = 0.0,
    k_scale: jnp.ndarray = None,
    v_scale: jnp.ndarray = None,
) -> jnp.ndarray:
    """Fused paged attention lowered through plain XLA (the non-TPU path).

    Same contract as the Pallas kernel: per-token block-table walk,
    unallocated-sentinel masking, zero rows for padding queries, optional
    per-row int8 dequant.  It gathers only each token's *own* pages (one
    (T, num_blocks) gather — never a whole-pool materialization), with
    unallocated blocks masked before the softmax, so the isolation
    guarantees match the kernel's.  On CPU this beats interpret-mode
    Pallas by >20x at serving shapes, which is why ``ops`` dispatches
    here off-TPU.
    """
    t, h, d = q.shape
    num_pages, page_size, kvh, _ = k_pool.shape
    nb = tables.shape[1]
    g = h // kvh
    valid_q = q_slots >= 0
    pages = tables[jnp.clip(q_slots, 0, tables.shape[0] - 1)]  # (T, NB)
    page_ok = (pages >= 0) & (pages < num_pages)  # sentinel AND negatives
    safe = jnp.where(page_ok, pages, 0)
    keys = k_pool[safe].astype(jnp.float32)  # (T, NB, ps, KV, D)
    vals = v_pool[safe].astype(jnp.float32)
    if (k_scale is not None) != (v_scale is not None):
        raise ValueError("pass both k_scale and v_scale, or neither")
    if k_scale is not None:
        keys = keys * k_scale[safe][..., None]
        vals = vals * v_scale[safe][..., None]
    keys = keys.reshape(t, nb * page_size, kvh, d)
    vals = vals.reshape(t, nb * page_size, kvh, d)
    qg = q.reshape(t, kvh, g, d).astype(jnp.float32) / math.sqrt(d)
    logits = jnp.einsum("thgd,tkhd->thgk", qg, keys)
    if softcap > 0.0:
        logits = softcap * jnp.tanh(logits / softcap)
    kpos = jnp.arange(nb * page_size)
    mask = (kpos[None, :] <= q_pos[:, None]) & valid_q[:, None]
    if window > 0:
        mask &= kpos[None, :] > q_pos[:, None] - window
    mask &= jnp.repeat(page_ok, page_size, axis=1)
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    # re-mask after softmax: a fully-masked query (every page hostile or
    # unallocated) must output zeros, not a uniform mix of gathered rows
    w = jax.nn.softmax(logits, axis=-1) * mask[:, None, None, :]
    out = jnp.einsum("thgk,tkhd->thgd", w, vals)
    out = jnp.where(valid_q[:, None, None, None], out, 0.0)
    return out.reshape(t, h, d).astype(q.dtype)
