"""SSD intra-chunk Pallas TPU kernel (Mamba-2's dominant compute).

Computes, for one (sequence-chunk, head) tile, the within-chunk term of
the state-space dual form:

    y[i] = sum_{j<=i} (C_i . B_j) * exp(cum[i] - cum[j]) * dt[j] * x[j]

i.e. masked decay-weighted attention with scores C B^T — two (L,L)xP
matmuls on the MXU plus VPU elementwise for the decay mask, exactly the
blocked structure `repro.models.ssm._ssd_chunked` evaluates in jnp (which
is the oracle, `ref.ssd_chunk_ref`).  The inter-chunk recurrence stays in
lax.scan (short serial dimension), matching the SSD paper's split.

Grid: (batch * n_chunks, heads).  VMEM per instance at L=256, N=128,
P=64 fp32: CB scores 256x256 + decay 256x256 + x/y 256x64 + B/C 256x128
~ 0.8 MiB — comfortably double-buffered.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _decay_attention(x_ref, dt_ref, cum_row_ref, cum_col_ref, b_ref, c_ref, keep):
    """(C B^T) * exp(cum_i - cum_j) * dt_j over the admissible (i, j), times x.

    Per-position vectors arrive twice where both orientations are needed —
    as a (1, L) row and an (L, 1) column — so the kernel builds its (L, L)
    terms by broadcasting, with no in-kernel transpose.
    """
    x = x_ref[...].astype(jnp.float32)  # (L, P)
    dt = dt_ref[...].astype(jnp.float32)  # (1, L)
    cum_row = cum_row_ref[...].astype(jnp.float32)  # (1, L) cumulative log-decay
    cum_col = cum_col_ref[...].astype(jnp.float32)  # (L, 1)
    b = b_ref[...].astype(jnp.float32)  # (L, N)
    c = c_ref[...].astype(jnp.float32)  # (L, N)

    scores = jax.lax.dot_general(
        c, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (L, L): C_i . B_j
    decay = jnp.exp(-jnp.where(keep, cum_col - cum_row, 0.0)) * keep
    att = scores * decay * dt
    return jnp.dot(att, x, preferred_element_type=jnp.float32)


def _causal(l):
    return jax.lax.broadcasted_iota(jnp.int32, (l, l), 0) >= jax.lax.broadcasted_iota(
        jnp.int32, (l, l), 1
    )


def _ssd_chunk_kernel(x_ref, dt_ref, cum_row_ref, cum_col_ref, b_ref, c_ref, o_ref):
    keep = _causal(x_ref.shape[0])
    o_ref[...] = _decay_attention(
        x_ref, dt_ref, cum_row_ref, cum_col_ref, b_ref, c_ref, keep
    ).astype(o_ref.dtype)


def _ssd_segment_kernel(
    x_ref, dt_ref, cum_row_ref, cum_col_ref, b_ref, c_ref, seg_row_ref, seg_col_ref, o_ref
):
    seg_row = seg_row_ref[...]  # (1, T) int32
    seg_col = seg_col_ref[...]  # (T, 1)
    keep = _causal(x_ref.shape[0]) & (seg_col == seg_row) & (seg_col >= 0)
    o_ref[...] = _decay_attention(
        x_ref, dt_ref, cum_row_ref, cum_col_ref, b_ref, c_ref, keep
    ).astype(o_ref.dtype)


def ssd_segment(
    x: jnp.ndarray,  # (T, H, P) packed tokens
    dt: jnp.ndarray,  # (T, H)
    cum: jnp.ndarray,  # (T, H) cumulative log-decay over the packed axis
    b: jnp.ndarray,  # (T, N)
    c: jnp.ndarray,  # (T, N)
    seg: jnp.ndarray,  # (T,) int32 segment (slot) ids; < 0 = padding
    interpret: bool = False,
) -> jnp.ndarray:
    """Segment-masked SSD term for token-packed layouts, (T, H, P).

    The packed analogue of the intra-chunk term: one (T, T) decay-weighted
    score matmul per head, with the causal mask intersected with a
    same-segment mask so flattened requests stay isolated (the same move
    ``flash_attention`` makes with q/kv_segment_ids).  Oracle:
    ``ref.ssd_segment_ref``.

    TPU blocking: operands are laid out head-major so every block's last
    two dimensions are whole array dimensions; B and C are shared by all
    heads and fetched once.
    """
    t, h, p = x.shape
    n = b.shape[-1]
    xr = x.transpose(1, 0, 2)  # (H, T, P)
    dt_row = dt.T[:, None, :]  # (H, 1, T)
    cum_row = cum.T[:, None, :]
    cum_col = cum.T[:, :, None]  # (H, T, 1)
    seg = seg.astype(jnp.int32)

    out = pl.pallas_call(
        _ssd_segment_kernel,
        grid=(h,),
        in_specs=[
            pl.BlockSpec((None, t, p), lambda j: (j, 0, 0)),
            pl.BlockSpec((None, 1, t), lambda j: (j, 0, 0)),
            pl.BlockSpec((None, 1, t), lambda j: (j, 0, 0)),
            pl.BlockSpec((None, t, 1), lambda j: (j, 0, 0)),
            pl.BlockSpec((t, n), lambda j: (0, 0)),
            pl.BlockSpec((t, n), lambda j: (0, 0)),
            pl.BlockSpec((1, t), lambda j: (0, 0)),
            pl.BlockSpec((t, 1), lambda j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, t, p), lambda j: (j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((h, t, p), x.dtype),
        interpret=interpret,
    )(xr, dt_row, cum_row, cum_col, b, c, seg[None, :], seg[:, None])
    return out.transpose(1, 0, 2)


def ssd_chunk(
    x: jnp.ndarray,  # (B, NC, L, H, P)
    dt: jnp.ndarray,  # (B, NC, L, H)
    cum: jnp.ndarray,  # (B, NC, L, H) cumulative log-decay within chunk
    b: jnp.ndarray,  # (B, NC, L, N)
    c: jnp.ndarray,  # (B, NC, L, N)
    interpret: bool = False,
) -> jnp.ndarray:
    """Intra-chunk SSD term, (B, NC, L, H, P).

    Blocked like ``ssd_segment``: head-major operands, B/C fetched once per
    chunk (the grid runs heads innermost).
    """
    bs, nc, l, h, p = x.shape
    n = b.shape[-1]
    g = bs * nc

    xr = x.reshape(g, l, h, p).transpose(0, 2, 1, 3)  # (G, H, L, P)
    cum_t = cum.reshape(g, l, h).transpose(0, 2, 1)  # (G, H, L)
    dt_row = dt.reshape(g, l, h).transpose(0, 2, 1)[:, :, None, :]  # (G, H, 1, L)
    cum_row = cum_t[:, :, None, :]
    cum_col = cum_t[:, :, :, None]  # (G, H, L, 1)
    br = b.reshape(g, l, n)
    cr = c.reshape(g, l, n)

    out = pl.pallas_call(
        _ssd_chunk_kernel,
        grid=(g, h),
        in_specs=[
            pl.BlockSpec((None, None, l, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((None, None, 1, l), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((None, None, 1, l), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((None, None, l, 1), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((None, l, n), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, l, n), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, l, p), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, h, l, p), x.dtype),
        interpret=interpret,
    )(xr, dt_row, cum_row, cum_col, br, cr)
    return out.transpose(0, 2, 1, 3).reshape(bs, nc, l, h, p)
