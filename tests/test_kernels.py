"""Pallas kernel validation: interpret-mode sweeps vs pure-jnp oracles.

The kernels on the serving path (``paged_flash_attention``, bf16 and int8
pools, hostile block tables; ``ssd_segment``) keep a few fast cases in
tier-1, so an API break in Pallas shows on every run; the wide sweeps
are in the slow lane.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa
from repro.kernels import ops, ref


def rand(shape, dtype, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return x.astype(dtype)


@pytest.mark.slow
class TestFlashAttention:
    @pytest.mark.parametrize("b,h,kv,s,d", [
        (1, 4, 4, 128, 64),   # MHA
        (2, 4, 2, 256, 64),   # GQA
        (1, 8, 1, 256, 32),   # MQA
        (2, 2, 2, 384, 128),  # non-pow2 seq multiple of block
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_causal_sweep(self, b, h, kv, s, d, dtype):
        q = rand((b, h, s, d), dtype, 0)
        k = rand((b, kv, s, d), dtype, 1)
        v = rand((b, kv, s, d), dtype, 2)
        out = ops.flash_attention(q, k, v, causal=True, block_q=128, block_k=128, interpret=True)
        expect = ref.flash_attention_ref(q, k, v, causal=True)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(expect, np.float32), atol=tol
        )

    @pytest.mark.parametrize("window", [64, 128])
    def test_sliding_window(self, window):
        q = rand((1, 4, 256, 64), jnp.float32, 3)
        k = rand((1, 2, 256, 64), jnp.float32, 4)
        v = rand((1, 2, 256, 64), jnp.float32, 5)
        out = ops.flash_attention(q, k, v, causal=True, window=window, interpret=True)
        expect = ref.flash_attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5)

    def test_non_causal(self):
        q = rand((1, 2, 128, 64), jnp.float32, 6)
        k = rand((1, 2, 128, 64), jnp.float32, 7)
        v = rand((1, 2, 128, 64), jnp.float32, 8)
        out = ops.flash_attention(q, k, v, causal=False, interpret=True)
        expect = ref.flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5)

    def test_block_size_invariance(self):
        q = rand((1, 2, 256, 64), jnp.float32, 9)
        k = rand((1, 2, 256, 64), jnp.float32, 10)
        v = rand((1, 2, 256, 64), jnp.float32, 11)
        o1 = ops.flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
        o2 = ops.flash_attention(q, k, v, block_q=128, block_k=256, interpret=True)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


@pytest.mark.slow
class TestFlashAttentionSegments:
    """Segment-id masking — the packed-serving mask term (one flattened
    sequence holding several requests; queries must stay inside their own
    request's rows)."""

    @staticmethod
    def contiguous_segments(b, s, boundaries, seed=0):
        """(B, S) int32 segment ids, contiguous runs split at `boundaries`."""
        seg = np.zeros((b, s), np.int32)
        for bnd in boundaries:
            seg[:, bnd:] += 1
        return jnp.asarray(seg)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_segment_mask_matches_ref(self, dtype):
        b, h, kv, s, d = 2, 4, 2, 256, 64
        q = rand((b, h, s, d), dtype, 0)
        k = rand((b, kv, s, d), dtype, 1)
        v = rand((b, kv, s, d), dtype, 2)
        seg = self.contiguous_segments(b, s, [96, 160])
        out = ops.flash_attention(
            q, k, v, causal=True, interpret=True,
            q_segment_ids=seg, kv_segment_ids=seg,
        )
        expect = ref.flash_attention_ref(
            q, k, v, causal=True, q_segment_ids=seg, kv_segment_ids=seg
        )
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(expect, np.float32), atol=tol
        )

    def test_segment_mask_with_window(self):
        q = rand((1, 4, 256, 64), jnp.float32, 3)
        k = rand((1, 2, 256, 64), jnp.float32, 4)
        v = rand((1, 2, 256, 64), jnp.float32, 5)
        seg = self.contiguous_segments(1, 256, [128])
        out = ops.flash_attention(
            q, k, v, causal=True, window=64, interpret=True,
            q_segment_ids=seg, kv_segment_ids=seg,
        )
        expect = ref.flash_attention_ref(
            q, k, v, causal=True, window=64, q_segment_ids=seg, kv_segment_ids=seg
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5)

    def test_no_cross_segment_leak(self):
        """Adversarial: two packed slots whose *absolute* positions overlap.

        Both segments are causally visible to the second one's queries
        (they sit earlier in the flattened sequence), and segment 0's
        values are poisoned with a huge offset — any leak through the
        mask shows up at full magnitude.  Segment 1's rows must equal an
        attention computed over segment 1 alone.
        """
        s, half = 256, 128
        q = rand((1, 2, s, 64), jnp.float32, 6)
        k = rand((1, 2, s, 64), jnp.float32, 7)
        v = rand((1, 2, s, 64), jnp.float32, 8)
        v = v.at[:, :, :half].add(1e4)  # poison segment 0's values
        seg = self.contiguous_segments(1, s, [half])
        out = ops.flash_attention(
            q, k, v, causal=True, interpret=True,
            q_segment_ids=seg, kv_segment_ids=seg,
        )
        alone = ops.flash_attention(
            q[:, :, half:], k[:, :, half:], v[:, :, half:],
            causal=True, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(out[:, :, half:]), np.asarray(alone), atol=2e-5
        )
        assert np.asarray(out[:, :, half:]).max() < 1e3, "segment-0 poison leaked"
        # and the unsegmented kernel DOES see the poison (mask is load-bearing)
        leaky = ops.flash_attention(q, k, v, causal=True, interpret=True)
        assert np.asarray(leaky[:, :, half:]).max() > 1e3


class TestPagedAttention:
    """Gather-by-block-table attention (the repro.serve.kv Paged layout):
    kernel vs jnp oracle, paged-vs-contiguous equivalence, and the
    adversarial cross-page-leak check."""

    @staticmethod
    def scenario(num_slots=3, num_blocks=4, page_size=16, kvh=2, h=4, d=32,
                 seed=0, lens=(50, 17, 64)):
        """Random per-slot KV histories scattered over an interleaved page
        pool, plus one packed query token per slot at its last position."""
        rng = np.random.default_rng(seed)
        num_pages = num_slots * num_blocks
        # interleave page ownership across slots: slot s gets pages
        # s, s+num_slots, ... — physically discontiguous on purpose
        tables = np.full((num_slots, num_blocks), num_pages, np.int32)
        k_pool = rng.normal(size=(num_pages, page_size, kvh, d)).astype(np.float32)
        v_pool = rng.normal(size=(num_pages, page_size, kvh, d)).astype(np.float32)
        contig_k, contig_v = [], []
        for s, n in enumerate(lens):
            nb = -(-n // page_size)
            pages = [s + j * num_slots for j in range(nb)]
            tables[s, :nb] = pages
            contig_k.append(np.concatenate([k_pool[p] for p in pages], axis=0))
            contig_v.append(np.concatenate([v_pool[p] for p in pages], axis=0))
        q = rng.normal(size=(num_slots, h, d)).astype(np.float32)
        q_pos = np.asarray([n - 1 for n in lens], np.int32)
        q_slots = np.arange(num_slots, dtype=np.int32)
        return (jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
                jnp.asarray(tables), jnp.asarray(q_pos), jnp.asarray(q_slots),
                contig_k, contig_v)

    @pytest.mark.parametrize("window", [0, 24])
    def test_kernel_matches_ref(self, window):
        q, kp, vp, tbl, pos, slots, _, _ = self.scenario(seed=1)
        out = ops.paged_flash_attention(q, kp, vp, tbl, pos, slots,
                                        window=window, interpret=True)
        expect = ref.paged_attention_ref(q, kp, vp, tbl, pos, slots, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5)

    def test_bf16_pool_matches_ref(self):
        """bf16 queries and pools (the serving dtype): f32 accumulation
        inside the kernel keeps it within bf16 rounding of the oracle."""
        q, kp, vp, tbl, pos, slots, _, _ = self.scenario(seed=5)
        q, kp, vp = (x.astype(jnp.bfloat16) for x in (q, kp, vp))
        out = ops.paged_flash_attention(q, kp, vp, tbl, pos, slots, interpret=True)
        expect = ref.paged_attention_ref(q, kp, vp, tbl, pos, slots)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(expect, np.float32), atol=2e-2
        )

    def test_hostile_table_entries_masked(self):
        """Negative and past-the-pool entries in a live slot's table are
        masked exactly like the unallocated sentinel: the slot attends only
        to its real pages, and a slot whose every entry is hostile comes
        out all-zero."""
        q, kp, vp, tbl, pos, slots, _, _ = self.scenario(seed=6)
        num_pages = kp.shape[0]
        hostile = np.asarray(tbl).copy()
        hostile[0, 1] = -1  # a hole in the middle of slot 0's history
        hostile[1, :] = [-7, num_pages + 3, -1, num_pages]  # nothing real
        hostile = jnp.asarray(hostile)
        out = np.asarray(ops.paged_flash_attention(
            q, kp, vp, hostile, pos, slots, interpret=True))
        expect = np.asarray(ref.paged_attention_ref(q, kp, vp, hostile, pos, slots))
        np.testing.assert_allclose(out, expect, atol=2e-5)
        np.testing.assert_array_equal(out[1], np.zeros_like(out[1]))
        clean = np.asarray(ops.paged_flash_attention(
            q, kp, vp, tbl, pos, slots, interpret=True))
        assert np.abs(out[0] - clean[0]).max() > 1e-3  # the hole was dropped

    def test_matches_contiguous_oracle(self):
        """Each slot's paged output must equal dense attention over that
        slot's logically-contiguous KV alone."""
        q, kp, vp, tbl, pos, slots, ck, cv = self.scenario(seed=2)
        out = np.asarray(
            ops.paged_flash_attention(q, kp, vp, tbl, pos, slots, interpret=True)
        )
        for s in range(q.shape[0]):
            n = int(pos[s]) + 1
            dense = ref.flash_attention_ref(
                jnp.asarray(q[s][None, :, None]),  # (1, H, 1, D)
                jnp.asarray(ck[s][None, :n]).transpose(0, 2, 1, 3),
                jnp.asarray(cv[s][None, :n]).transpose(0, 2, 1, 3),
                causal=True,
            )
            np.testing.assert_allclose(
                out[s], np.asarray(dense)[0, :, 0], atol=2e-5
            )

    def test_padding_query_is_zero(self):
        q, kp, vp, tbl, pos, slots, _, _ = self.scenario(seed=3)
        slots = slots.at[1].set(-1)
        out = np.asarray(
            ops.paged_flash_attention(q, kp, vp, tbl, pos, slots, interpret=True)
        )
        np.testing.assert_array_equal(out[1], np.zeros_like(out[1]))

    def test_no_cross_page_leak(self):
        """Adversarial: poison every page the query's slot does NOT own
        with a huge value.  The block-table gather must make other slots'
        pages structurally unreachable — any leak shows at full magnitude."""
        q, kp, vp, tbl, pos, slots, ck, cv = self.scenario(seed=4)
        own = set(int(p) for p in np.asarray(tbl[0]) if p < kp.shape[0])
        poison = np.asarray([p for p in range(kp.shape[0]) if p not in own])
        vp = vp.at[poison].add(1e4)
        out = np.asarray(
            ops.paged_flash_attention(q, kp, vp, tbl, pos, slots, interpret=True)
        )
        n = int(pos[0]) + 1
        alone = ref.flash_attention_ref(
            jnp.asarray(q[0][None, :, None]),
            jnp.asarray(ck[0][None, :n]).transpose(0, 2, 1, 3),
            jnp.asarray(cv[0][None, :n]).transpose(0, 2, 1, 3),
            causal=True,
        )
        np.testing.assert_allclose(out[0], np.asarray(alone)[0, :, 0], atol=2e-5)
        assert np.abs(out[0]).max() < 1e3, "foreign page values leaked"
        # ...and a table pointing AT the poisoned pages does see them
        # (the isolation comes from the table, not luck)
        tbl_bad = tbl.at[0].set(tbl[1])
        leaky = np.asarray(
            ops.paged_flash_attention(q, kp, vp, tbl_bad, pos, slots, interpret=True)
        )
        assert np.abs(leaky[0]).max() > 1e3

    @staticmethod
    def packed_scenario(page_size=16, kvh=2, h=4, d=16, seed=0,
                       lens=(20, 9, 16)):
        """Mixed-phase packed queries over one pool: slot 0 decodes (its
        last position), slot 1 verifies (a 4-token tail), slot 2 prefills
        (every position) — the three shapes the engine routes through the
        one fused path."""
        rng = np.random.default_rng(seed)
        num_slots = len(lens)
        nb = max(-(-n // page_size) for n in lens)
        num_pages = num_slots * nb
        tables = np.full((num_slots, nb), num_pages, np.int32)
        k_pool = rng.normal(size=(num_pages, page_size, kvh, d)).astype(np.float32)
        v_pool = rng.normal(size=(num_pages, page_size, kvh, d)).astype(np.float32)
        for s, n in enumerate(lens):
            for j in range(-(-n // page_size)):
                tables[s, j] = s + j * num_slots  # interleaved ownership
        spans = [range(lens[0] - 1, lens[0]),          # decode
                 range(max(lens[1] - 4, 0), lens[1]),  # verify tail
                 range(lens[2])]                       # packed prefill
        q_pos = np.asarray([p for sp in spans for p in sp], np.int32)
        q_slots = np.asarray(
            [s for s, sp in enumerate(spans) for _ in sp], np.int32)
        q = rng.normal(size=(len(q_pos), h, d)).astype(np.float32)
        return (jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
                jnp.asarray(tables), jnp.asarray(q_pos), jnp.asarray(q_slots))

    @staticmethod
    def quantize_pool(pool):
        """Per-(token-row, kv-head) symmetric int8, the model's scheme."""
        pool = np.asarray(pool)
        amax = np.abs(pool).max(axis=-1)
        scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
        codes = np.clip(np.round(pool / scale[..., None]), -127, 127)
        return jnp.asarray(codes.astype(np.int8)), jnp.asarray(scale)

    @pytest.mark.slow
    @pytest.mark.parametrize("g", [1, 2, 4])
    @pytest.mark.parametrize("page_size", [4, 16])
    @pytest.mark.parametrize("window", [0, 7])
    def test_fused_matrix(self, g, page_size, window):
        """decode/verify/packed-prefill through the fused path across GQA
        group sizes, page sizes, and sliding windows — both the XLA
        lowering (the off-TPU dispatch) and the interpreted kernel must
        match the jnp oracle."""
        kvh = 2
        q, kp, vp, tbl, pos, slots = self.packed_scenario(
            page_size=page_size, kvh=kvh, h=g * kvh, seed=g + page_size)
        expect = np.asarray(ref.paged_attention_ref(
            q, kp, vp, tbl, pos, slots, window=window))
        xla = np.asarray(ops.paged_flash_attention(
            q, kp, vp, tbl, pos, slots, window=window))
        kern = np.asarray(ops.paged_flash_attention(
            q, kp, vp, tbl, pos, slots, window=window, interpret=True))
        np.testing.assert_allclose(xla, expect, atol=2e-5)
        np.testing.assert_allclose(kern, expect, atol=2e-5)

    def test_int8_matches_dequantized_ref(self):
        """int8 pools + scales through both fused paths == the float ref
        over the dequantized pools (the quantization is the only error
        source; the attention math must be bit-for-bit the same)."""
        q, kp, vp, tbl, pos, slots = self.packed_scenario(seed=11)
        kq, ks = self.quantize_pool(kp)
        vq, vs = self.quantize_pool(vp)
        deq_k = jnp.asarray(np.asarray(kq, np.float32) * np.asarray(ks)[..., None])
        deq_v = jnp.asarray(np.asarray(vq, np.float32) * np.asarray(vs)[..., None])
        expect = np.asarray(ref.paged_attention_ref(
            q, deq_k, deq_v, tbl, pos, slots))
        for interp in (None, True):
            out = np.asarray(ops.paged_flash_attention(
                q, kq, vq, tbl, pos, slots, k_scale=ks, v_scale=vs,
                interpret=interp))
            np.testing.assert_allclose(out, expect, atol=2e-5)

    def test_int8_quantization_error_bounded(self):
        """End-to-end int8 error against the unquantized oracle stays
        within the per-row quantization budget (~amax/127 per element)."""
        q, kp, vp, tbl, pos, slots = self.packed_scenario(seed=12)
        kq, ks = self.quantize_pool(kp)
        vq, vs = self.quantize_pool(vp)
        exact = np.asarray(ref.paged_attention_ref(q, kp, vp, tbl, pos, slots))
        out = np.asarray(ops.paged_flash_attention(
            q, kq, vq, tbl, pos, slots, k_scale=ks, v_scale=vs))
        # v rows are convex-combined, so output error is bounded by the
        # worst per-element v quantization error plus the softmax shift
        # from the k error; normal(0,1) rows quantize at ~3sigma/127
        np.testing.assert_allclose(out, exact, atol=0.1)
        assert np.abs(out - exact).max() > 0  # int8 is not bit-identical

    def test_int8_no_cross_page_leak(self):
        """Poison foreign pages in the int8 pools (max code, huge scale):
        slot 0's output must be identical to the unpoisoned run."""
        q, kp, vp, tbl, pos, slots = self.packed_scenario(seed=13)
        kq, ks = self.quantize_pool(kp)
        vq, vs = self.quantize_pool(vp)
        clean = np.asarray(ops.paged_flash_attention(
            q, kq, vq, tbl, pos, slots, k_scale=ks, v_scale=vs,
            interpret=True))
        own = set(int(p) for p in np.asarray(tbl[0]) if p < kq.shape[0])
        poison = np.asarray([p for p in range(kq.shape[0]) if p not in own])
        vq = vq.at[poison].set(127)
        vs = vs.at[poison].set(1e4)
        out = np.asarray(ops.paged_flash_attention(
            q, kq, vq, tbl, pos, slots, k_scale=ks, v_scale=vs,
            interpret=True))
        sel = np.asarray(slots) == 0
        np.testing.assert_array_equal(out[sel], clean[sel])
        assert np.abs(out[sel]).max() < 1e3, "foreign int8 pages leaked"

    # (slot, first position, tokens) runs in packed order; 4 slots of 48
    # 16-token pages, so each slot's table spans two key tiles
    # (fa.PAGED_KEY_TILE = 512 keys) and query blocks (paged_block_tokens:
    # 16 tokens at 24 query tokens, 4 slots, G = 2) both have boundaries
    # inside the runs.  With window 24, a block whose first query lies past
    # 512 + 24 starts its key loop at the second tile (lo > 0).
    LAYOUTS = {
        # the decode-capacity shape: one token per slot, 4 tokens; slot 3
        # reads its second key tile
        "all_decode": (4, [(0, 150, 1), (1, 37, 1), (2, 5, 1), (3, 700, 1)]),
        # starts mid-page, crosses pages, the 512-key tile and a block
        "long_chunk": (24, [(1, 500, 20)]),
        # a cell's mixed step in little: decodes between two chunks, one
        # chunk wholly in the second key tile
        "chunks_and_decodes": (24, [(1, 90, 1), (0, 60, 8), (3, 170, 1),
                                    (2, 560, 10)]),
        # slot 2 in two runs, a key tile apart, around another slot's token
        "split_slot": (24, [(2, 40, 6), (0, 100, 1), (2, 540, 4)]),
        # every token a run of its own: the slots alternate, across 512
        "token_runs": (24, [(i % 4, 490 + 7 * (i // 4), 1) for i in range(24)]),
        "all_padding": (24, []),
    }

    @classmethod
    def layout_scenario(cls, name, kv_dtype, seed=0, page_size=16, kvh=2, h=4, d=16):
        t, runs = cls.LAYOUTS[name]
        rng = np.random.default_rng(seed)
        num_slots, nb = 4, 768 // page_size  # 48 pages of 16
        num_pages = num_slots * nb
        q_slots = np.full(t, -1, np.int32)
        q_pos = np.zeros(t, np.int32)
        c = 0
        for s, p0, n in runs:
            q_slots[c:c + n], q_pos[c:c + n] = s, np.arange(p0, p0 + n)
            c += n
        tables = np.full((num_slots, nb), num_pages, np.int32)
        for s in range(num_slots):
            live = q_pos[q_slots == s].max(initial=0) // page_size + 1
            tables[s, :live] = [s + j * num_slots for j in range(live)]
        k_pool = rng.normal(size=(num_pages, page_size, kvh, d)).astype(np.float32)
        v_pool = rng.normal(size=(num_pages, page_size, kvh, d)).astype(np.float32)
        q = jnp.asarray(rng.normal(size=(t, h, d)), jnp.bfloat16)
        addr = tuple(jnp.asarray(a) for a in (tables, q_pos, q_slots))
        if kv_dtype == "int8":
            (kq, ks), (vq, vs) = cls.quantize_pool(k_pool), cls.quantize_pool(v_pool)
            return (q, kq, vq) + addr, {"k_scale": ks, "v_scale": vs}
        pools = tuple(jnp.asarray(p, jnp.bfloat16) for p in (k_pool, v_pool))
        return (q,) + pools + addr, {}

    @pytest.mark.parametrize("softcap", [0.0, 2.0])
    @pytest.mark.parametrize("window", [0, 24])
    @pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
    @pytest.mark.parametrize("layout", sorted(LAYOUTS))
    def test_layouts_match_ref(self, layout, kv_dtype, window, softcap):
        """Every packed layout a caller can pass -- runs of any length,
        a slot in several runs, every token its own run, no tokens at
        all -- comes out as the oracle says: no token dropped, padding
        rows zero."""
        args, scales = self.layout_scenario(layout, kv_dtype)
        out = np.asarray(ops.paged_flash_attention(
            *args, window=window, softcap=softcap, interpret=True, **scales), np.float32)
        expect = np.asarray(ref.paged_attention_ref(
            *args, window=window, softcap=softcap, **scales), np.float32)
        np.testing.assert_allclose(out, expect, atol=2e-2)
        pad = np.asarray(args[-1]) < 0
        np.testing.assert_array_equal(out[pad], np.zeros_like(out[pad]))
        assert np.abs(out[~pad]).max(initial=1.0) > 0  # real rows are not zeroed

    @pytest.mark.parametrize("page_size", [4, 8])
    def test_page_mask_words(self, page_size):
        """Pages under 16 rows put more than 32 pages in a 512-key tile, so
        the kernel reads which table entries are real from several 32-bit
        words: hostile entries in every word, and in the second key tile,
        stay masked as the oracle masks them."""
        args, _ = self.layout_scenario("split_slot", "bf16", page_size=page_size)
        q, kp, vp, tables, q_pos, q_slots = args
        n = kp.shape[0]
        per_tile = fa.PAGED_KEY_TILE // page_size
        hostile = np.asarray(tables).copy()
        # slot 2 reads to position 543: one entry in each word of its first
        # tile and one in its second
        cols = [5 + 32 * w for w in range(per_tile // 32)] + [per_tile + 1]
        hostile[2, cols] = [-1, n + 3, -7, n][: len(cols) - 1] + [-1]
        hostile = jnp.asarray(hostile)
        out = np.asarray(ops.paged_flash_attention(
            q, kp, vp, hostile, q_pos, q_slots, interpret=True), np.float32)
        expect = np.asarray(ref.paged_attention_ref(
            q, kp, vp, hostile, q_pos, q_slots), np.float32)
        np.testing.assert_allclose(out, expect, atol=2e-2)
        clean = np.asarray(ops.paged_flash_attention(
            q, kp, vp, tables, q_pos, q_slots, interpret=True), np.float32)
        slot2 = np.asarray(q_slots) == 2
        assert np.abs(out[slot2] - clean[slot2]).max() > 1e-2  # the holes were dropped


class TestSsdSegment:
    """Segment-masked SSD term (the packed serving path of 'M' layers)."""

    @staticmethod
    def packed_inputs(t=48, h=3, p=16, n=8, seed=0):
        """Three contiguous requests (slots 2, 0, 1) then padding (-1),
        with dt zeroed on padding as the model's packed branch does."""
        rng = np.random.default_rng(seed)
        seg = np.full(t, -1, np.int32)
        seg[0:20], seg[20:31], seg[31:44] = 2, 0, 1
        dt = rng.uniform(0.1, 0.9, (t, h)).astype(np.float32)
        dt[seg < 0] = 0.0
        a = rng.uniform(0.1, 1.0, (h,)).astype(np.float32)
        cum = np.cumsum(dt * a, axis=0)
        x = rng.normal(size=(t, h, p)).astype(np.float32)
        b = rng.normal(size=(t, n)).astype(np.float32)
        c = rng.normal(size=(t, n)).astype(np.float32)
        return tuple(jnp.asarray(v) for v in (x, dt, cum, b, c, seg))

    def test_matches_ref(self):
        x, dt, cum, b, c, seg = self.packed_inputs()
        out = ops.ssd_segment(x, dt, cum, b, c, seg, interpret=True)
        expect = ref.ssd_segment_ref(x, dt, cum, b, c, seg)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expect), atol=1e-4, rtol=1e-4
        )

    def test_segments_isolated_and_padding_zero(self):
        """Poisoning one request's inputs moves only that request's rows;
        padding rows are exactly zero."""
        x, dt, cum, b, c, seg = self.packed_inputs(seed=1)
        clean = np.asarray(ops.ssd_segment(x, dt, cum, b, c, seg, interpret=True))
        first = np.asarray(seg) == 2
        x_bad = x.at[jnp.asarray(first)].add(1e4)
        out = np.asarray(ops.ssd_segment(x_bad, dt, cum, b, c, seg, interpret=True))
        others = np.asarray(seg) != 2
        np.testing.assert_array_equal(out[others], clean[others])
        assert np.abs(out[first]).max() > 1e3
        pad = np.asarray(seg) < 0
        np.testing.assert_array_equal(out[pad], np.zeros_like(out[pad]))


@pytest.mark.slow
class TestRmsnorm:
    @pytest.mark.parametrize("shape", [(4, 128), (3, 17, 256), (1, 1, 1024), (513, 128)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, shape, dtype):
        x = rand(shape, dtype, 0)
        s = rand((shape[-1],), jnp.float32, 1)
        out = ops.rmsnorm(x, s, interpret=True)
        expect = ref.rmsnorm_ref(x, s)
        tol = 3e-2 if dtype == jnp.bfloat16 else 1e-6
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(expect, np.float32), atol=tol
        )


@pytest.mark.slow
class TestMaskedAccum:
    @pytest.mark.parametrize("n", [128, 1000, 65536 + 3])
    @pytest.mark.parametrize("gdtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, n, gdtype):
        acc = rand((n,), jnp.float32, 0)
        g = rand((n,), gdtype, 1)
        for keep in (0.0, 1.0):
            out = ops.masked_accum(acc, g, jnp.float32(keep), scale=0.125, interpret=True)
            expect = ref.masked_accum_ref(acc, g, jnp.float32(keep), 0.125)
            np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-6)

    def test_tree_variant(self):
        accs = {"a": rand((64,), jnp.float32, 2), "b": rand((32, 8), jnp.float32, 3)}
        gs = {"a": rand((64,), jnp.bfloat16, 4), "b": rand((32, 8), jnp.bfloat16, 5)}
        out = ops.masked_accum_tree(accs, gs, jnp.float32(1.0), interpret=True)
        for k in accs:
            expect = ref.masked_accum_ref(accs[k], gs[k], jnp.float32(1.0))
            np.testing.assert_allclose(np.asarray(out[k]), np.asarray(expect), atol=1e-6)

    def test_matches_dropcompute_semantics(self):
        """keep=0 must leave the accumulator untouched (Algorithm 1 line 8)."""
        acc = rand((257,), jnp.float32, 6)
        g = rand((257,), jnp.float32, 7)
        out = ops.masked_accum(acc, g, jnp.float32(0.0), interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(acc))
