"""Token-packed serving step: packed-vs-dense parity, typed pattern
errors, and the slow-lane packed soak.

The dense (B, chunk_size) engine is the oracle: for every point on the
parity matrix (budget x chunk x mixed prompt lengths) the packed engine
must produce identical greedy output streams, TTFT step counts, and
per-step scheduled/deferred-token accounting — packing changes *which
compute runs*, never *what is computed*.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import ModelConfig
from repro.models.model import (
    UnsupportedPatternError,
    init_decode_cache,
    init_params,
    packed_prefill,
    prefill_chunk,
)
from repro.serve import ContinuousBatcher, Request, pack_step, packed_capacity

CFG = ModelConfig(
    name="serve-packed-t", n_layers=2, d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
    vocab_size=101, layer_pattern="LG", sliding_window=6, dtype="float32", remat=False,
)

# mixed prompt lengths through 2 slots: forces slot reuse and mixed
# decode+prefill steps (the shapes where packing actually differs)
PROMPT_LENS = (3, 5, 12, 4, 8)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def make_prompts(seed=0, lens=PROMPT_LENS):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, size=n).tolist() for n in lens]


def run_engine(params, prompts, packed, max_new=4, **kw):
    kw.setdefault("batch_slots", 2)
    kw.setdefault("max_len", 24)
    eng = ContinuousBatcher(params, CFG, packed=packed, **kw)
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=list(p), max_new_tokens=max_new))
    eng.run()
    return eng


class TestPackedDenseParity:
    """Dense engine as oracle across the budget x chunk matrix."""

    @pytest.mark.parametrize("budget", [None, 4, 16])
    @pytest.mark.parametrize("chunk", [4, 16])
    def test_parity_matrix(self, params, budget, chunk):
        prompts = make_prompts()
        dense = run_engine(params, prompts, packed=False,
                           chunk_size=chunk, token_budget=budget)
        packd = run_engine(params, prompts, packed=True,
                           chunk_size=chunk, token_budget=budget)

        # identical greedy output streams (byte-identical token ids)
        assert {u: r.output for u, r in dense.finished.items()} == {
            u: r.output for u, r in packd.finished.items()
        }
        # identical TTFT step counts per request
        assert {u: r.ttft_steps for u, r in dense.finished.items()} == {
            u: r.ttft_steps for u, r in packd.finished.items()
        }
        # identical per-step scheduling and deferral accounting
        assert dense.steps == packd.steps
        for sd, sp in zip(dense.step_stats, packd.step_stats):
            assert (sd.decode_tokens, sd.prefill_tokens, sd.deferred_tokens) == (
                sp.decode_tokens, sp.prefill_tokens, sp.deferred_tokens
            )

    def test_parity_token_streaming_chunk1(self, params):
        """chunk=1 is the seed token-streaming degenerate case."""
        prompts = make_prompts(seed=1, lens=(3, 6, 4))
        dense = run_engine(params, prompts, packed=False, chunk_size=1)
        packd = run_engine(params, prompts, packed=True, chunk_size=1)
        assert {u: r.output for u, r in dense.finished.items()} == {
            u: r.output for u, r in packd.finished.items()
        }

    def test_packed_capacity_is_the_compiled_shape(self, params):
        """The packed program shape is capacity, not (B, chunk)."""
        assert packed_capacity(2, 4, None) == 8
        assert packed_capacity(2, 4, 4) == 5
        assert packed_capacity(8, 16, 4) == 9  # decode slots dominate
        eng = run_engine(params, make_prompts(seed=2, lens=(5,)), packed=True,
                         chunk_size=4, token_budget=4)
        assert eng.packed_capacity == 5

    def test_packed_budget_never_overflows_capacity(self, params):
        """Every step's granted tokens fit the compiled packed shape
        (pack_step raises on overflow, so completing is the assertion);
        also check the accounting against the documented bound."""
        prompts = make_prompts(seed=3, lens=(20, 3, 3, 18))
        eng = run_engine(params, prompts, packed=True, batch_slots=3,
                         max_len=32, chunk_size=8, token_budget=4, max_new=6)
        for s in eng.step_stats:
            assert s.scheduled_tokens <= eng.packed_capacity
            assert s.scheduled_tokens <= max(s.decode_tokens, 4) + 1


class TestPackingLayout:
    """Deterministic layout checks (the hypothesis sweep lives in
    test_property.py)."""

    def test_pack_step_layout(self):
        grants = [(0, 5, [11]), (2, 0, [21, 22, 23]), (1, 7, [31, 32])]
        lay = pack_step(grants, capacity=8)
        assert lay.n_tokens == 6 and lay.capacity == 8
        np.testing.assert_array_equal(
            lay.tokens, [11, 21, 22, 23, 31, 32, 0, 0])
        np.testing.assert_array_equal(
            lay.slot_ids, [0, 2, 2, 2, 1, 1, -1, -1])
        np.testing.assert_array_equal(
            lay.positions, [5, 0, 1, 2, 7, 8, 0, 0])
        np.testing.assert_array_equal(lay.segment_starts, [0, 1, 4, 6])
        assert lay.spans == {0: (0, 1), 2: (1, 3), 1: (4, 2)}

    def test_pack_step_overflow_raises(self):
        with pytest.raises(ValueError, match="overflow"):
            pack_step([(0, 0, [1, 2, 3])], capacity=2)

    def test_zero_token_grants_occupy_nothing(self):
        lay = pack_step([(0, 4, []), (1, 0, [7])], capacity=2)
        assert lay.n_tokens == 1 and lay.spans == {1: (0, 1)}


class TestPackedModelPath:
    """packed_prefill vs prefill_chunk at the model level."""

    def test_packed_matches_chunked(self, params):
        prompts = make_prompts(seed=4, lens=(7, 3))
        b, max_len, chunk = 2, 24, 4
        # dense chunked reference
        cache_d = init_decode_cache(params, CFG, b, max_len, linear=True)
        cache_p = init_decode_cache(params, CFG, b, max_len, linear=True)
        pos = [0, 0]
        last_d, last_p = {}, {}
        while any(pos[i] < len(prompts[i]) for i in range(b)):
            toks = np.zeros((b, chunk), np.int32)
            lens = np.zeros(b, np.int32)
            grants = []
            for i, p in enumerate(prompts):
                n = min(chunk, len(p) - pos[i])
                lens[i] = n
                toks[i, :n] = p[pos[i]: pos[i] + n]
                if n:
                    grants.append((i, pos[i], p[pos[i]: pos[i] + n]))
            lg_d, cache_d = prefill_chunk(
                params, CFG, cache_d, jnp.asarray(toks),
                jnp.asarray(pos, jnp.int32), jnp.asarray(lens))
            jax.block_until_ready(lg_d)
            lay = pack_step(grants, capacity=b * chunk)
            lg_p, cache_p = packed_prefill(
                params, CFG, cache_p, jnp.asarray(lay.tokens),
                jnp.asarray(lay.slot_ids), jnp.asarray(lay.positions))
            jax.block_until_ready(lg_p)
            for i, p in enumerate(prompts):
                if lens[i] and pos[i] + lens[i] == len(p):
                    last_d[i] = np.asarray(lg_d[i, lens[i] - 1])
                    j0, m = lay.spans[i]
                    last_p[i] = np.asarray(lg_p[j0 + m - 1])
                pos[i] += int(lens[i])
        for i in last_d:
            np.testing.assert_allclose(last_p[i], last_d[i], atol=1e-5)
            assert int(last_p[i].argmax()) == int(last_d[i].argmax())

    def test_head_idx_selects_rows(self, params):
        """``head_idx`` returns the logits of exactly those packed rows
        (any order, repeats allowed) and leaves the cache as without it."""
        grants = [(0, 0, make_prompts(seed=5, lens=(6,))[0]), (1, 0, [7])]
        lay = pack_step(grants, capacity=10)
        packed = [jnp.asarray(a) for a in (lay.tokens, lay.slot_ids, lay.positions)]
        cache = init_decode_cache(params, CFG, 2, 24, linear=True)
        full, cache_f = packed_prefill(params, CFG, cache, *packed)
        idx = np.asarray([6, 5, 0, 6], np.int32)
        part, cache_h = packed_prefill(params, CFG, cache, *packed,
                                       head_idx=jnp.asarray(idx))
        assert part.shape == (4, CFG.vocab_size)
        np.testing.assert_allclose(part, np.asarray(full)[idx], atol=1e-6)
        assert jax.tree.all(jax.tree.map(
            lambda a, b: bool((np.asarray(a) == np.asarray(b)).all()), cache_f, cache_h))


class TestHeadRows:
    """Which packed programs gather the rows that can emit before the LM
    head, and the counter that says so (``StepStats.rows``/``head_rows``)."""

    def _engine(self, params, **kw):
        return ContinuousBatcher(params, CFG, batch_slots=2, max_len=24, chunk_size=4,
                                 packed=True, cache="paged", page_size=4, **kw)

    def test_decode_program_is_untouched(self, params, monkeypatch):
        from repro.serve import scheduler

        eng = self._engine(params, token_budget=8)
        assert (eng.head_rows, eng.packed_capacity, eng.packed_decode_capacity) == (2, 9, 2)
        calls, step = [], scheduler._packed_engine_step

        def spy(*a, head_idx=None, **kw):
            calls.append((a[3].shape[0], None if head_idx is None else head_idx.shape))
            return step(*a, head_idx=head_idx, **kw)

        monkeypatch.setattr(scheduler, "_packed_engine_step", spy)
        for i, p in enumerate(make_prompts(lens=(7, 2, 5))):
            eng.submit(Request(uid=i, prompt=p, max_new_tokens=4))
        eng.run()
        assert set(calls) == {(2, None), (9, (2,))}
        # the decode-capacity program without the argument and with None
        # is one program
        vec = jax.ShapeDtypeStruct((2,), jnp.int32)
        lower = lambda **kw: step.lower(  # noqa: E731
            eng.params, eng.cfg, eng.cache, vec, vec, vec, moe_impl="dense", **kw).as_text()
        assert lower() == lower(head_idx=None)
        assert lower() != lower(head_idx=vec)

    def test_step_stats_count_head_rows(self, params):
        eng = self._engine(params, token_budget=8)
        for i, p in enumerate(make_prompts(lens=(7, 2, 5))):
            eng.submit(Request(uid=i, prompt=p, max_new_tokens=4))
        eng.run()
        kinds = {(st.kind, st.rows, st.head_rows) for st in eng.step_stats}
        assert kinds == {("decode", 2, 2), ("mixed", 9, 2)}
        # the dense engine sends every row of its (B, C) program
        dense = ContinuousBatcher(params, CFG, batch_slots=2, max_len=24, chunk_size=4)
        dense.submit(Request(uid=0, prompt=make_prompts(lens=(7,))[0], max_new_tokens=3))
        dense.run()
        assert {(st.kind, st.rows, st.head_rows) for st in dense.step_stats} == {
            ("mixed", 8, 8), ("decode", 2, 2)}


class TestUnsupportedPatternTyped:
    """Non-chunkable configs raise the typed error cleanly (asserts would
    vanish under python -O).  'R'/'M' patterns chunk-scan through the
    serving paths now; what remains unservable is bidirectional 'B'
    layers (no causal cache) and, for recurrent patterns, speculative
    decoding (carried state cannot roll back rejected drafts)."""

    @pytest.mark.parametrize("pattern", ["R", "M"])
    def test_spec_with_recurrent_raises(self, pattern):
        from repro.serve.spec import NGramProposer, SpecConfig

        bad = ModelConfig(name="bad", n_layers=2, d_model=32, n_heads=2,
                          n_kv_heads=1, d_ff=64, vocab_size=101,
                          layer_pattern=pattern, dtype="float32", remat=False)
        with pytest.raises(UnsupportedPatternError, match="roll back"):
            ContinuousBatcher({}, bad, batch_slots=1, max_len=8,
                              spec=SpecConfig(proposer=NGramProposer()))

    @pytest.mark.parametrize("fn", [prefill_chunk, packed_prefill])
    @pytest.mark.parametrize("pattern", ["BG", "B"])
    def test_model_paths_raise(self, fn, pattern):
        bad = ModelConfig(name="bad", n_layers=2, d_model=32, n_heads=2,
                          n_kv_heads=1, d_ff=64, vocab_size=101,
                          layer_pattern=pattern, dtype="float32", remat=False)
        with pytest.raises(UnsupportedPatternError, match="layer patterns"):
            fn({}, bad, {}, jnp.zeros((4,) if fn is packed_prefill else (1, 4), jnp.int32),
               jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32))

    def test_is_typed_not_assert(self):
        assert issubclass(UnsupportedPatternError, NotImplementedError)
        assert not issubclass(UnsupportedPatternError, AssertionError)


@pytest.mark.slow
class TestPackedSoak:
    """End-to-end packed serving soak: 64 staggered requests."""

    def test_soak_no_starvation_budget_honored(self, params):
        rng = np.random.default_rng(7)
        budget, slots, chunk, max_len = 12, 8, 16, 64
        eng = ContinuousBatcher(params, CFG, batch_slots=slots, max_len=max_len,
                                chunk_size=chunk, token_budget=budget, packed=True)
        lens = rng.integers(4, 40, size=64)
        pending = [
            Request(uid=i, prompt=rng.integers(0, CFG.vocab_size, size=n).tolist(),
                    max_new_tokens=8)
            for i, n in enumerate(lens)
        ]
        # staggered arrivals: a few new requests every few steps
        while pending or eng.busy:
            for _ in range(3):
                if pending:
                    eng.submit(pending.pop(0))
            for _ in range(4):
                if eng.busy:
                    eng.step()
        done = eng.finished
        # no starvation: every request finished and emitted its tokens
        assert sorted(done) == list(range(64))
        assert all(len(r.output) == 8 for r in done.values())
        assert all(r.ttft_steps is not None for r in done.values())
        for s in eng.step_stats:
            # budget honored: decode is unconditional, prefill fills the
            # remainder, the starvation guard may add one token
            assert s.scheduled_tokens <= max(s.decode_tokens, budget) + 1
            assert s.scheduled_tokens <= eng.packed_capacity
            # starvation guard: whenever prefill work waited, some ran
            if s.deferred_tokens > 0:
                assert s.prefill_tokens >= 1
