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


# keys a loop iteration meets: 512 // page_size pages.  On a v5e a 512-key
# tile took 0.59 ms a layer for a docs-like mixed step where 128 keys took
# 0.91, and 0.29 ms against 0.53 for a decode step; 1,024 keys gained
# under 10% more for twice the unrolled page loads.
PAGED_KEY_TILE = 512
PAGED_MAX_ROWS = 256  # query rows (tokens x group) a block may hold
PAGED_NARROW_ROWS = 16  # a block with no more real rows takes the narrow path
# concurrent DMAs that stage one KV head's K pool, and as many its V pool:
# on a v5e, 8 staged a 1,184-page pool for a decode-capacity call in 0.15 ms
# where a double-buffered block spec (one DMA each) took 0.22
PAGED_STAGE_DMAS = 8
# two heads' staged pools, double-buffered, outgrow the compiler's 16 MiB
# default for a kernel at the cells' 1,184 pages
PAGED_VMEM_LIMIT = 32 * 2**20


def paged_block_tokens(t: int, num_slots: int, g: int) -> int:
    """Query tokens per block, from the call's static shape alone.

    One slot can hold at most ``t - num_slots + 1`` of the ``t`` query
    tokens while every other slot holds one; the block is the largest
    power of two not above that, capped at ``PAGED_MAX_ROWS`` rows, and
    never under 8 rows.  A decode-capacity call (``t <= num_slots``)
    gets the smallest block; a packed call with room for prefill chunks
    gets 128-token blocks (at a group of 2).
    """
    most = max(t - num_slots + 1, 1)
    bq = min(1 << (most.bit_length() - 1), max(PAGED_MAX_ROWS // g, 1))
    return max(bq, -(-8 // g))


def paged_block_bound(t: int, num_slots: int, bq: int) -> int:
    """Most blocks any layout of ``t`` tokens over ``num_slots`` slots
    needs: a slot holding n tokens takes ceil(n / bq) blocks, and at most
    min(num_slots, t) slots hold any, so the sum is at most
    (t + min(num_slots, t) * (bq - 1)) // bq."""
    return max((t + min(num_slots, t) * (bq - 1)) // bq, 1)


def _paged_attn_kernel(
    start_ref, okbits_ref, slot_ref, lo_ref, hi_ref, rows_ref, q_ref, qpos_ref,
    k_hbm, v_hbm, *rest, page_size, num_pages, pages_per_tile, blocks_per_slot,
    window, sm_scale, softcap, quantized,
):
    if quantized:
        ks_ref, vs_ref, o_ref, k_buf, v_buf, sem = rest
    else:
        o_ref, k_buf, v_buf, sem = rest
    # Stage the head's pool into VMEM at its first block, and start the
    # next head's into the other buffer: heads run outermost, so each
    # head's pool is fetched once a call, behind the previous head's work.
    j, nh = pl.program_id(0), pl.num_programs(0)
    n_dma = sem.shape[-1]
    edges = [round(c * num_pages / n_dma) * page_size for c in range(n_dma + 1)]

    def staging(head, buf):
        out = []
        for c in range(n_dma):
            r = pl.ds(edges[c], edges[c + 1] - edges[c])
            out.append(pltpu.make_async_copy(k_hbm.at[head, r], k_buf.at[buf, r], sem.at[buf, 0, c]))
            out.append(pltpu.make_async_copy(v_hbm.at[head, r], v_buf.at[buf, r], sem.at[buf, 1, c]))
        return out

    cur = j % 2
    first = pl.program_id(1) == 0

    @pl.when(first & (j == 0))
    def _():
        for c in staging(0, 0):
            c.start()

    @pl.when(first)
    def _():
        for c in staging(j, cur):
            c.wait()

        @pl.when(j + 1 < nh)
        def _():
            for c in staging(j + 1, 1 - cur):
                c.start()

    k_ref, v_ref = k_buf.at[cur], v_buf.at[cur]
    # scalar-prefetched (SMEM) addressing for this program's query block
    b = pl.program_id(1)
    slot, lo, hi = slot_ref[b], lo_ref[b], hi_ref[b]
    tk = pages_per_tile * page_size
    cdt = q_ref.dtype if quantized else jnp.promote_types(q_ref.dtype, k_ref.dtype)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1)
    lane_page = lane // page_size  # the tile's page that holds each key
    n_words = -(-pages_per_tile // 32)

    def attend(rows):
        q = q_ref[:rows, :].astype(cdt)  # (rows, D): tokens x group, as stored
        qpos = qpos_ref[:rows, :]  # (rows, 1); -1 on a padding row

        def body(ki, carry):
            acc, m_prev, l_prev = carry
            # one key tile: pages_per_tile entries of the slot's table, each
            # the first row of a page_size-row slice of the staged pool.
            # Unallocated (num_pages) and hostile (negative) entries were
            # clamped into the pool by the wrapper, so they only ever read
            # a page that their bit, one 32-page word at a time, masks.
            base = slot * blocks_per_slot + ki * pages_per_tile
            page_ok = None
            for w in range(n_words):
                word = jnp.full((1, tk), okbits_ref[(base // pages_per_tile) * n_words + w],
                                jnp.int32)
                bit = jax.lax.shift_right_logical(word, lane_page % 32) & 1
                page_ok = bit if page_ok is None else jnp.where(lane_page // 32 == w, bit, page_ok)
            k_parts, v_parts = [], []
            for p in range(pages_per_tile):
                start = pl.multiple_of(start_ref[base + p], page_size)
                k_parts.append(k_ref[pl.ds(start, page_size), :])
                v_parts.append(v_ref[pl.ds(start, page_size), :])
            k_tile = jax.lax.concatenate(k_parts, 0).astype(cdt)  # (tk, D)
            v_tile = jax.lax.concatenate(v_parts, 0).astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k_tile, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale  # (rows, tk)
            if quantized:
                # int8 pages: per-row dequant scales, gathered by the
                # table into the slot's logical row, applied to the
                # scores and to the softmax weights -- the same sums as
                # scaling the K/V rows, without the (tk, D) multiplies
                s = s * ks_ref[slot, pl.ds(ki, 1), :]
            if softcap > 0.0:
                s = softcap * jnp.tanh(s / softcap)
            kpos = ki * tk + lane
            mask = (kpos <= qpos) & (page_ok != 0)
            if window > 0:
                mask &= kpos > qpos - window
            s = jnp.where(mask, s, NEG_INF)
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            # mask p explicitly: when every position so far is masked, m_cur
            # is still NEG_INF and exp(s - m_cur) would be 1, not 0 -- a
            # fully-masked query must come out all-zero
            p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
            l_cur = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            if quantized:
                p = p * vs_ref[slot, pl.ds(ki, 1), :]
            # p stays float32 into the product: no rounding of the weights
            acc = acc * alpha + jnp.dot(p, v_tile, preferred_element_type=jnp.float32)
            return acc, m_cur, l_cur

        d = q.shape[-1]
        acc0 = jnp.zeros((rows, d), jnp.float32)
        m0 = jnp.full((rows, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((rows, 1), jnp.float32)
        acc, _, l = jax.lax.fori_loop(lo, hi, body, (acc0, m0, l0))
        o_ref[:rows, :] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

    full = q_ref.shape[0]
    if full <= PAGED_NARROW_ROWS:
        attend(full)
    else:
        # a block holding a decode or a short verify run computes on its
        # first PAGED_NARROW_ROWS rows only; its other rows are padding
        # that the wrapper never reads back
        narrow = rows_ref[b] <= PAGED_NARROW_ROWS

        @pl.when(narrow)
        def _():
            attend(PAGED_NARROW_ROWS)

        @pl.when(jnp.logical_not(narrow))
        def _():
            attend(full)


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
    (what the jnp path does), the kernel walks the slot's *block table*
    and loads each key tile by its page ids -- the loop never reads
    another slot's pages, so cross-request isolation is structural, not a
    mask (adversarially tested in ``tests/test_kernels.py``).  Entries
    with ``tables[s, b] >= num_pages`` (the unallocated sentinel) or
    ``< 0`` are mask-dropped; padding queries (``q_slots < 0``) return
    zero rows.

    Blocks: the wrapper sorts the query tokens by slot (stably, padding
    last) and cuts each slot's tokens into blocks of ``Bq``
    (``paged_block_tokens``: from ``T``, the slot count and the group
    size G alone -- 4 tokens at G = 2 for a decode-capacity call, 128
    for a packed step with prefill room).  Every block belongs to one
    slot, in any layout: a slot in two separate runs, or every token a
    run of its own, only merges or splits blocks, and each row keeps its
    own position for the mask.  At most ``(T + min(S, T) * (Bq - 1)) //
    Bq`` blocks exist (``paged_block_bound``), which sizes the gathered
    ``(KV, blocks, Bq * G, D)`` query array, so no token is ever dropped;
    the grid's second axis is the number of blocks actually formed
    (a dynamic grid bound), so empty blocks cost nothing.

    Grid ``(KV heads, blocks)``: one program per (KV head, query block)
    runs the online softmax for the block's ``Bq * G`` rows (the head's
    whole query group) over key tiles of ``512 // page_size`` pages (512
    keys), from the window's lower bound for the block's first query to
    the causal bound of its last; a per-row mask handles the rest.  A
    block with at most 16 real rows (a decode, a short verify run)
    computes on those 16 rows only.  ``q . K^T`` multiplies the operands
    as stored (bf16) with float32 accumulation and scales the float32
    scores; the softmax state and the weights ``p`` stay float32 into the
    ``p . V`` product.  Scalar prefetch (SMEM) holds each table entry's
    first row in the staged pool, which entries name a real page (packed
    as bits, one 32-bit word per 32 pages of a key tile, so a tile's page
    mask is one shift per word), and the per-block slot, loop bounds and
    row counts: the kernel's per-page work is two loads, which keeps its
    lowering short (the key tile's pages are unrolled).

    Each KV head's pool is *staged* whole into VMEM by the kernel's own
    DMAs (``PAGED_STAGE_DMAS`` concurrent copies each for K and V) from
    HBM, where the wrapper keeps it: the head's first block waits for its
    pool and starts the next head's into a second buffer, so each head's
    pool is fetched once a call, behind the previous head's work, and the
    kernel's device time holds its own reads.  Reads inside the staged
    pool are pruned by the loop bounds.  Two heads' pools bound the pool
    by the kernel's VMEM limit (``PAGED_VMEM_LIMIT``, 32 MiB).  At
    internlm2-1.8b widths (8 KV heads, head_dim 128, 16-token pages, 32
    slots of 256 blocks, 513 query tokens) the largest pool that compiles
    for a v5e is 1,944 pages (31,104 tokens) with bf16 pages and 3,888
    pages (62,208 tokens) with int8 pages; 8 pages more and the compiler
    runs out of VMEM.  Lifting that bound needs a kernel that DMAs pages
    by table entry from an HBM-resident pool.

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

    bq = paged_block_tokens(t, num_slots, g)
    nblk = paged_block_bound(t, num_slots, bq)
    rows = bq * g
    ppt = max(PAGED_KEY_TILE // page_size, 1)
    tk = ppt * page_size
    # tables padded with the sentinel to whole key tiles
    bps = -(-num_blocks // ppt) * ppt
    tables = jnp.pad(tables.astype(jnp.int32), ((0, 0), (0, bps - num_blocks)),
                     constant_values=num_pages)
    # which entries name a real page, as bits: word w of a key tile holds
    # its pages 32w..32w+31 (distinct bits, so the sum is their OR)
    n_words = -(-ppt // 32)
    ok = ((tables >= 0) & (tables < num_pages)).reshape(num_slots, bps // ppt, ppt)
    ok = jnp.pad(ok, ((0, 0), (0, 0), (0, n_words * 32 - ppt))).astype(jnp.uint32)
    ok = ok.reshape(num_slots, bps // ppt, n_words, 32) << jnp.arange(32, dtype=jnp.uint32)
    okbits = jax.lax.bitcast_convert_type(ok.sum(-1, dtype=jnp.uint32), jnp.int32)
    # each entry's first row in the flat staged pool; entries the mask drops
    # read page 0 or the last page, in range
    starts = jnp.clip(tables, 0, num_pages - 1) * page_size

    # --- blocks, in the graph: sort by slot, cut each slot's run at bq ---
    valid = q_slots >= 0
    key = jnp.where(valid, jnp.clip(q_slots, 0, num_slots - 1), num_slots)
    order = jnp.argsort(key, stable=True)
    key_s = key[order]
    counts = jnp.zeros((num_slots + 1,), jnp.int32).at[key].add(1)
    rank = jnp.arange(t) - (jnp.cumsum(counts) - counts)[key_s]
    slot_blocks = -(-counts[:num_slots] // bq)
    n_blocks = jnp.sum(slot_blocks)
    slot_first = jnp.cumsum(slot_blocks) - slot_blocks
    blk_s = jnp.where(key_s < num_slots,
                      slot_first[jnp.minimum(key_s, num_slots - 1)] + rank // bq, nblk)
    dest = jnp.zeros((t,), jnp.int32).at[order].set(blk_s * bq + rank % bq)
    src = jnp.full((nblk * bq,), t, jnp.int32).at[dest].set(
        jnp.arange(t, dtype=jnp.int32), mode="drop")  # token per block row; t = none
    pos_s = q_pos.astype(jnp.int32)[order]
    blk_slot = jnp.zeros((nblk,), jnp.int32).at[blk_s].set(key_s, mode="drop")
    blk_rows = jnp.zeros((nblk,), jnp.int32).at[blk_s].add(g, mode="drop")
    blk_max = jnp.zeros((nblk,), jnp.int32).at[blk_s].max(pos_s, mode="drop")
    blk_hi = jnp.minimum(blk_max // tk + 1, bps // ppt)
    if window > 0:
        blk_min = jnp.full((nblk,), jnp.iinfo(jnp.int32).max, jnp.int32).at[blk_s].min(
            pos_s, mode="drop")
        blk_lo = jnp.maximum((blk_min - window + 1) // tk, 0)
    else:
        blk_lo = jnp.zeros((nblk,), jnp.int32)

    # (KV, blocks, rows, D): a block's rows are its tokens x the head's group
    qt = jnp.pad(q.reshape(t, kvh, g, d), ((0, 1), (0, 0), (0, 0), (0, 0)))
    qb = qt.transpose(1, 0, 2, 3)[:, src].reshape(kvh, nblk, rows, d)
    qpos = jnp.where(src < t, jnp.pad(q_pos.astype(jnp.int32), (0, 1))[src], -1)
    qpos = jnp.repeat(qpos, g).reshape(nblk, rows, 1)
    # (KV, num_pages * page_size, D): one flat row pool per KV head, so a
    # page id turns into a row-slice start inside the kernel.
    n_rows = num_pages * page_size
    kr = k_pool.transpose(2, 0, 1, 3).reshape(kvh, n_rows, d)
    vr = v_pool.transpose(2, 0, 1, 3).reshape(kvh, n_rows, d)
    if not interpret:  # (the interpreter takes no memory-space constraint)
        # The pools stay in HBM and the kernel's own DMAs stage them.
        # Otherwise XLA may place one in VMEM ahead of the call (a v5e has
        # room for a 38.8 MB pool), and its transfer then hides in the
        # producing op's time instead of the kernel's.  A trade, not a
        # speed-up: on a v5e XLA's placement with a block spec made a chat
        # decode step 18.35 ms, this 20.29.  It exists because
        # bench/metrics/paged_attn_roofline.docs.py charges the pool's
        # reads to the kernel; once that reader stops depending on where
        # the pools live, drop this pin, the staging DMAs and
        # test_paged_pools_stay_in_hbm together.
        kr, vr = (pltpu.with_memory_space_constraint(x, pltpu.HBM) for x in (kr, vr))

    kernel = functools.partial(
        _paged_attn_kernel,
        page_size=page_size, num_pages=num_pages, pages_per_tile=ppt,
        blocks_per_slot=bps, window=window, sm_scale=1.0 / math.sqrt(d),
        softcap=softcap, quantized=quantized,
    )
    # index maps take (grid indices..., *scalar prefetch refs)
    in_specs = [
        pl.BlockSpec((None, None, rows, d), lambda j, i, *_: (j, i, 0, 0)),
        pl.BlockSpec((None, rows, 1), lambda j, i, *_: (i, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),  # staged by the kernel itself
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [qb, qpos, kr, vr]
    if quantized:
        # Per-row dequant scales gathered by the (padded) table into one
        # (slots, key tiles, tk) plane per KV head, so a key tile's scales
        # are one row of it; entries the table does not own are masked in
        # the kernel like their pages.
        safe = jnp.clip(tables, 0, num_pages - 1)
        for sc in (k_scale, v_scale):
            plane = sc.astype(jnp.float32).transpose(2, 0, 1)[:, safe]
            in_specs.append(pl.BlockSpec((None, num_slots, bps // ppt, tk),
                                         lambda j, i, *_: (j, 0, 0, 0)))
            operands.append(plane.reshape(kvh, num_slots, bps // ppt, tk))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(kvh, n_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, None, rows, d), lambda j, i, *_: (j, i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, n_rows, d), k_pool.dtype),
            pltpu.VMEM((2, n_rows, d), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2, min(PAGED_STAGE_DMAS, num_pages))),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((kvh, nblk, rows, d), q.dtype),
        interpret=interpret,
        name="paged_flash_attention",
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=PAGED_VMEM_LIMIT),
    )(starts.reshape(num_slots * bps), okbits.reshape(-1), blk_slot, blk_lo, blk_hi,
      blk_rows, *operands)
    # blocks past n_blocks are never written, and no token's row is there
    out = out.reshape(kvh, nblk * bq, g, d)[:, jnp.clip(dest, 0, nblk * bq - 1)]
    out = jnp.where(valid[None, :, None, None], out, 0).astype(q.dtype)
    return out.transpose(1, 0, 2, 3).reshape(t, h, d)


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
