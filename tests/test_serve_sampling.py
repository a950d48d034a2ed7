"""Per-request stochastic sampling: determinism, parity, spec exactness.

The contract under test (``serve.sampling``): output token ``i`` of a
request is sampled with ``fold_in(PRNGKey(seed), i)`` — a pure function
of (request seed, output index) — so seeded streams replay across engine
restarts and across the dense/packed/paged step programs, match a
single-request reference loop with the same keys, and stay
realization-identical when speculative decoding is on (rejection-
sampling acceptance, ``spec.accept_sampled``).  ``temperature == 0`` is
byte-identical to the pre-sampling argmax engine.

Also home of this PR's serving-path bugfix regressions: the
``DraftModelProposer`` recycled-slot/stale-history rewind and the
``StepStats.budget_overshoot`` accounting.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import ModelConfig
from repro.models.model import decode_step, init_decode_cache, init_params
from repro.serve import (
    ContinuousBatcher,
    DraftModelProposer,
    InvalidRequestError,
    NGramProposer,
    Proposer,
    Request,
    SamplingParams,
    SpecConfig,
    accept_greedy,
    accept_sampled,
    residual_sample,
    sample_one,
    sample_tokens,
)

CFG = ModelConfig(
    name="serve-samp-t", n_layers=2, d_model=32, n_heads=2, n_kv_heads=1,
    d_ff=64, vocab_size=101, layer_pattern="LG", sliding_window=6,
    dtype="float32", remat=False,
)

PROMPT_LENS = (3, 5, 12, 4, 8)

#: the stochastic point every parity test runs at (the BENCH sampled
#: rows use the same one)
SAMPLED = SamplingParams(temperature=0.8, top_p=0.95)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def make_prompts(seed=0, lens=PROMPT_LENS):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, size=n).tolist() for n in lens]


def seeded(i, base=SAMPLED):
    """Per-request params: distinct seeds inside one batch."""
    return base.with_seed(100 + i)


def run_engine(params, prompts, max_new=8, sampling=seeded, **kw):
    """Run every prompt through one engine; ``sampling`` maps request
    index -> SamplingParams (None = engine default, i.e. greedy)."""
    kw.setdefault("batch_slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("chunk_size", 16)
    eng = ContinuousBatcher(params, CFG, **kw)
    for i, p in enumerate(prompts):
        extra = {} if sampling is None else {"sampling": sampling(i)}
        eng.submit(Request(uid=i, prompt=list(p), max_new_tokens=max_new,
                           **extra))
    eng.run()
    return eng


def outputs(eng):
    return {u: r.output for u, r in eng.finished.items()}


def reference_stream(params, prompt, sp, max_new=8, max_len=32):
    """Single-request oracle: a one-slot ``decode_step`` loop, sampling
    each output token with ``sample_one`` and the same (seed, output
    index) keys the engine derives.  No engine code involved."""
    cache = init_decode_cache(params, CFG, 1, max_len, linear=True)
    toks = list(prompt)
    logits = None
    for t, tok in enumerate(toks):
        logits, cache = decode_step(
            params, CFG, cache, jnp.asarray([[tok]], jnp.int32),
            jnp.asarray([t], jnp.int32),
        )
    out = []
    for i in range(max_new):
        tok = sample_one(np.asarray(logits)[0, 0], sp, i)
        out.append(tok)
        logits, cache = decode_step(
            params, CFG, cache, jnp.asarray([[tok]], jnp.int32),
            jnp.asarray([len(toks) + i], jnp.int32),
        )
    return out


class JunkProposer(Proposer):
    """Deterministic junk drafts — near-total rejection, driving the
    rollback + residual-emission path on every verify step."""

    name = "junk"

    def __init__(self):
        self.calls = 0

    def propose_batch(self, asks):
        out = {}
        for slot, hist, k in asks:
            self.calls += 1
            out[slot] = [
                (hist[-1] * 7 + j * 13 + self.calls) % CFG.vocab_size
                for j in range(k)
            ]
        return out


# ---------------------------------------------------------------------------
# temperature=0 is byte-identical greedy
# ---------------------------------------------------------------------------


class TestGreedyByteIdentity:
    @pytest.fixture(scope="class")
    def oracle(self, params):
        # the pre-sampling engine: no sampling field at all
        return outputs(run_engine(params, make_prompts(), sampling=None))

    @pytest.mark.parametrize("budget", [None, 4, 16])
    @pytest.mark.parametrize("mode", ["dense", "packed", "paged"])
    def test_explicit_greedy_params_match_default(self, params, oracle,
                                                  mode, budget):
        """Explicit ``SamplingParams()`` (any seed) == the default-field
        engine across the {dense, packed, paged} x budget matrix."""
        kw = {}
        if mode == "packed":
            kw = dict(packed=True)
        elif mode == "paged":
            kw = dict(packed=True, cache="paged", page_size=4)
        eng = run_engine(
            params, make_prompts(), token_budget=budget,
            sampling=lambda i: SamplingParams(seed=17 + i), **kw,
        )
        assert outputs(eng) == oracle

    @pytest.mark.parametrize("budget", [None, 4, 16])
    def test_greedy_with_spec_unchanged(self, params, oracle, budget):
        eng = run_engine(
            params, make_prompts(), token_budget=budget,
            sampling=lambda i: SamplingParams(),
            spec=SpecConfig(NGramProposer(), k=4),
        )
        assert outputs(eng) == oracle


# ---------------------------------------------------------------------------
# seeded stochastic streams: reproducible, path-independent
# ---------------------------------------------------------------------------


class TestSampledParity:
    @pytest.fixture(scope="class")
    def oracle(self, params):
        return outputs(run_engine(params, make_prompts()))

    def test_restart_reproduces(self, params, oracle):
        """A fresh engine (new caches, new compilations) replays the
        exact streams: keys depend on nothing engine-lifetime."""
        assert outputs(run_engine(params, make_prompts())) == oracle

    @pytest.mark.parametrize("budget", [None, 4, 16])
    @pytest.mark.parametrize("mode", ["dense", "packed", "paged"])
    def test_step_path_matrix(self, params, oracle, mode, budget):
        """{dense, packed, paged} x budgets {None, 4, 16}: identical
        seeded streams — the packed per-token slot-gathered keys and the
        paged layout sample exactly what the dense oracle samples."""
        kw = {}
        if mode == "packed":
            kw = dict(packed=True)
        elif mode == "paged":
            kw = dict(packed=True, cache="paged", page_size=4)
        eng = run_engine(params, make_prompts(), token_budget=budget, **kw)
        assert outputs(eng) == oracle

    def test_matches_single_request_reference(self, params, oracle):
        """The batched engine == a no-engine decode_step loop sampling
        with the same (seed, output index) keys, per request."""
        for i, p in enumerate(make_prompts()):
            ref = reference_stream(params, p, seeded(i))
            assert ref == oracle[i], (i, ref, oracle[i])

    def test_greedy_reference_matches(self, params):
        """Same reference loop at temperature 0 == the greedy engine."""
        greedy = outputs(run_engine(params, make_prompts(), sampling=None))
        for i, p in enumerate(make_prompts()):
            ref = reference_stream(params, p, SamplingParams())
            assert ref == greedy[i]

    def test_distinct_seeds_independent(self, params):
        """Same prompt, same batch, different seeds -> different streams;
        same seed -> the same stream."""
        p = make_prompts()[2]
        eng = ContinuousBatcher(params, CFG, batch_slots=3, max_len=32,
                                chunk_size=16)
        for uid, seed in enumerate((1, 2, 1)):
            eng.submit(Request(uid=uid, prompt=list(p), max_new_tokens=8,
                               sampling=SAMPLED.with_seed(seed)))
        fin = eng.run()
        assert fin[0].output == fin[2].output  # seed 1 twice
        assert fin[0].output != fin[1].output  # seeds 1 vs 2

    def test_mixed_greedy_and_sampled_batch(self, params):
        """Greedy and stochastic requests share a batched step without
        perturbing each other: each matches its own solo reference."""
        prompts = make_prompts()
        eng = ContinuousBatcher(params, CFG, batch_slots=2, max_len=32,
                                chunk_size=16, token_budget=4)
        for i, p in enumerate(prompts):
            sp = SamplingParams() if i % 2 == 0 else seeded(i)
            eng.submit(Request(uid=i, prompt=list(p), max_new_tokens=8,
                               sampling=sp))
        got = {u: r.output for u, r in eng.run().items()}
        for i, p in enumerate(prompts):
            sp = SamplingParams() if i % 2 == 0 else seeded(i)
            assert got[i] == reference_stream(params, p, sp), i

    def test_top_k_and_top_p_thread_through(self, params):
        """Non-trivial filtering params change the stream and still
        replay (engine vs reference, not just engine vs engine)."""
        base = SamplingParams(temperature=1.5, top_k=7, top_p=0.8)
        eng = run_engine(params, make_prompts(),
                         sampling=lambda i: base.with_seed(50 + i),
                         packed=True, cache="paged", page_size=4)
        for i, p in enumerate(make_prompts()):
            ref = reference_stream(params, p, base.with_seed(50 + i))
            assert outputs(eng)[i] == ref


# ---------------------------------------------------------------------------
# rejection-sampling speculation == non-spec sampled streams
# ---------------------------------------------------------------------------


class TestSpecSampledExactness:
    @pytest.fixture(scope="class")
    def oracle(self, params):
        return outputs(run_engine(params, make_prompts()))

    @pytest.mark.parametrize("budget", [None, 4, 16])
    @pytest.mark.parametrize("cache", ["dense", "paged"])
    def test_ngram_matrix(self, params, oracle, cache, budget):
        eng = run_engine(
            params, make_prompts(), token_budget=budget, cache=cache,
            spec=SpecConfig(NGramProposer(), k=4),
        )
        assert outputs(eng) == oracle
        if eng.kv is not None:
            assert eng.kv.used_pages == 0

    @pytest.mark.parametrize("cache", ["dense", "paged"])
    def test_junk_proposer_rollback_exact(self, params, oracle, cache):
        """~0% acceptance under sampling: every step rejects drafts and
        emits the target's own sample (the residual-coupled token) after
        rolling the junk KV back — streams still exactly match."""
        eng = run_engine(
            params, make_prompts(), cache=cache,
            spec=SpecConfig(JunkProposer(), k=3),
        )
        assert outputs(eng) == oracle
        summ = eng.stats_summary()
        assert summ["draft_tokens"] > 0
        assert summ["acceptance_rate"] < 0.5  # junk rarely matches

    def test_draft_model_proposer_sampled_exact(self, params, oracle):
        prop = DraftModelProposer(params, CFG, batch_slots=2, max_len=32)
        eng = run_engine(params, make_prompts(), packed=True, cache="paged",
                         page_size=4, spec=SpecConfig(prop, k=3))
        assert outputs(eng) == oracle


# ---------------------------------------------------------------------------
# the LM head and sampler see only the rows that can emit a token
# ---------------------------------------------------------------------------


TOP_K = SamplingParams(temperature=1.5, top_k=7, top_p=0.8)
SHARED = make_prompts(seed=3, lens=(9,))[0]

#: case -> (prompts, sampling, engine options); chunks of 4 tokens
HEAD_CASES = {
    "greedy": (make_prompts(), lambda i: SamplingParams(), dict(token_budget=16)),
    "top_p": (make_prompts(), seeded, dict(token_budget=16)),
    "top_k": (make_prompts(), lambda i: TOP_K.with_seed(50 + i), dict(token_budget=16)),
    # every prompt completes exactly at the end of a chunk
    "chunk_boundary": (make_prompts(lens=(8, 12, 4, 16)), seeded, dict()),
    # every prompt completes inside a chunk
    "mid_chunk": (make_prompts(lens=(5, 9, 3, 14)), seeded, dict()),
    "prefix_shared": ([SHARED + p for p in make_prompts(seed=4, lens=(3, 6, 2, 5))],
                      seeded, dict(token_budget=16)),
    # verify grants of 1 + 2 columns: head rows 2 x 3 = 6 < capacity 17
    "spec_gathered": (make_prompts(), seeded, dict(token_budget=16, draft_k=2)),
    # capacity 5 <= 6 head rows: the gather is skipped
    "spec_skipped": (make_prompts(), seeded, dict(token_budget=4, draft_k=2)),
}


class TestHeadRowGather:
    """A packed program wider than ``ContinuousBatcher.head_rows`` sends
    only the rows that can emit through the LM head and the sampler; the
    streams are token-identical to the same engine fed every row, and to
    the dense engine (the parity oracle)."""

    @staticmethod
    def _packed(params, prompts, sampling, all_rows=False, token_budget=None,
                draft_k=0):
        spec = SpecConfig(NGramProposer(), k=draft_k) if draft_k else None
        eng = ContinuousBatcher(params, CFG, batch_slots=2, max_len=32,
                                chunk_size=4, packed=True, cache="paged",
                                page_size=4, token_budget=token_budget, spec=spec)
        if all_rows:
            eng.head_rows = eng.packed_capacity  # no program is wider
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=list(p), max_new_tokens=8,
                               sampling=sampling(i)))
        eng.run()
        return eng

    @pytest.mark.parametrize("case", sorted(HEAD_CASES))
    def test_streams_match_all_rows_and_dense(self, params, case):
        prompts, sampling, kw = HEAD_CASES[case]
        eng = self._packed(params, prompts, sampling, **kw)
        every = self._packed(params, prompts, sampling, all_rows=True, **kw)
        dense = run_engine(params, prompts, sampling=sampling, chunk_size=4,
                           token_budget=kw.get("token_budget"))
        assert outputs(eng) == outputs(every) == outputs(dense)
        gathered = [st for st in eng.step_stats if st.head_rows < st.rows]
        assert all(st.head_rows == st.rows for st in every.step_stats)
        if case == "spec_skipped":
            assert eng.packed_capacity <= eng.head_rows and not gathered
        else:
            assert gathered and all(st.head_rows == eng.head_rows for st in gathered)
        if case.startswith("spec"):
            assert eng.stats_summary()["draft_tokens"] > 0
        if case == "prefix_shared":
            assert eng.stats_summary()["shared_tokens"] > 0
        if case in ("chunk_boundary", "mid_chunk"):
            on_boundary = {len(p) % 4 == 0 for p in prompts}
            assert on_boundary == {case == "chunk_boundary"}


# ---------------------------------------------------------------------------
# sampler units: masking, validation, residual form
# ---------------------------------------------------------------------------


def _sample_rows(logits, *, seeds, oidx, t, tk=0, tp=1.0):
    n = logits.shape[0]
    return np.asarray(sample_tokens(
        jnp.asarray(logits),
        np.broadcast_to(np.asarray(seeds, np.uint32), (n,)),
        np.broadcast_to(np.asarray(oidx, np.int32), (n,)),
        np.broadcast_to(np.asarray(t, np.float32), (n,)),
        np.broadcast_to(np.asarray(tk, np.int32), (n,)),
        np.broadcast_to(np.asarray(tp, np.float32), (n,)),
    ))


class TestSamplerUnits:
    def setup_method(self):
        self.logits = np.asarray(
            np.random.default_rng(3).normal(size=(16, 33)), np.float32
        )

    def test_temperature_zero_is_argmax(self):
        got = _sample_rows(self.logits, seeds=9, oidx=4, t=0.0)
        np.testing.assert_array_equal(got, self.logits.argmax(-1))

    def test_top_k_one_is_argmax(self):
        got = _sample_rows(self.logits, seeds=9, oidx=4, t=1.7, tk=1)
        np.testing.assert_array_equal(got, self.logits.argmax(-1))

    def test_tiny_top_p_is_argmax(self):
        # the exclusive-cumsum form always keeps the top token
        got = _sample_rows(self.logits, seeds=9, oidx=4, t=1.7, tp=1e-9)
        np.testing.assert_array_equal(got, self.logits.argmax(-1))

    def test_top_k_support_respected(self):
        k = 5
        top = np.argsort(self.logits, axis=-1)[:, -k:]
        for idx in range(6):
            got = _sample_rows(self.logits, seeds=123, oidx=idx, t=5.0, tk=k)
            for row, tok in enumerate(got):
                assert tok in top[row]

    def test_key_depends_on_seed_and_index(self):
        a = _sample_rows(self.logits, seeds=1, oidx=0, t=1.5)
        b = _sample_rows(self.logits, seeds=2, oidx=0, t=1.5)
        c = _sample_rows(self.logits, seeds=1, oidx=1, t=1.5)
        a2 = _sample_rows(self.logits, seeds=1, oidx=0, t=1.5)
        np.testing.assert_array_equal(a, a2)
        assert not np.array_equal(a, b)
        assert not np.array_equal(a, c)

    def test_sample_one_agrees_with_batch(self):
        sp = SamplingParams(temperature=0.9, top_k=11, top_p=0.7, seed=42)
        got = np.asarray(sample_tokens(
            jnp.asarray(self.logits),
            np.full((16,), sp.seed, np.uint32),
            np.arange(16, dtype=np.int32),
            np.full((16,), sp.temperature, np.float32),
            np.full((16,), sp.top_k, np.int32),
            np.full((16,), sp.top_p, np.float32),
        ))
        for i in range(16):
            assert sample_one(self.logits[i], sp, i) == got[i]

    def test_params_validation(self):
        for bad in (dict(temperature=-0.1), dict(temperature=float("nan")),
                    dict(top_k=-1), dict(top_p=0.0), dict(top_p=1.5),
                    dict(seed="abc")):
            with pytest.raises(ValueError):
                SamplingParams(**bad)

    def test_engine_rejects_non_params(self, params):
        eng = ContinuousBatcher(params, CFG, batch_slots=1, max_len=16)
        req = Request(uid=0, prompt=[1, 2], max_new_tokens=2)
        req.sampling = {"temperature": 1.0}  # duck-typed stand-in
        with pytest.raises(InvalidRequestError):
            eng.submit(req)

    def test_accept_sampled_prefix_and_greedy_alias(self):
        assert accept_sampled([5, 6, 7], [5, 6, 9, 0]) == (2, [5, 6, 9])
        assert accept_sampled([1], [2, 3]) == (0, [2])
        assert accept_sampled([], [4]) == (0, [4])
        assert accept_greedy([5, 6], [5, 6, 7]) == \
            accept_sampled([5, 6], [5, 6, 7])

    def test_residual_sample_marginal(self):
        """MC check of the residual distribution norm(max(p - q, 0)):
        per-token frequencies over many fixed keys match the analytic
        residual (and the q==p degenerate case falls back to p)."""
        v = 6
        logits = jnp.asarray([0.5, 1.5, -0.3, 0.9, 0.0, -1.0], jnp.float32)
        p = np.asarray(jax.nn.softmax(logits))
        q = np.zeros(v, np.float32)
        q[1] = 1.0  # one-hot draft at the mode
        resid = np.maximum(p - q, 0)
        resid /= resid.sum()
        n = 4000
        keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(n, dtype=jnp.uint32))
        toks = np.asarray(jax.vmap(
            lambda k: residual_sample(logits, jnp.asarray(q), k)
        )(keys))
        freq = np.bincount(toks, minlength=v) / n
        assert freq[1] == 0.0  # the drafted token never resamples
        np.testing.assert_allclose(freq, resid, atol=0.03)
        # degenerate q == p: falls back to p itself
        toks = np.asarray(jax.vmap(
            lambda k: residual_sample(logits, jnp.asarray(p), k)
        )(keys))
        freq = np.bincount(toks, minlength=v) / n
        np.testing.assert_allclose(freq, p, atol=0.03)

    def test_coupled_acceptance_marginal_is_target(self):
        """The engine's coupling (sample x ~ p per column, accept the
        one-hot draft iff x == d) has the rejection-sampling marginal:
        emitted-token frequencies == p, and P(accept) == p(d)."""
        v = 6
        logits = np.asarray([0.2, 1.1, -0.5, 0.7, -0.2, 0.4], np.float32)
        p = np.asarray(jax.nn.softmax(jnp.asarray(logits)))
        d = 3  # drafted token
        n = 4000
        rows = np.broadcast_to(logits, (n, v))
        toks = _sample_rows(rows, seeds=np.arange(n), oidx=0, t=1.0)
        freq = np.bincount(toks, minlength=v) / n
        np.testing.assert_allclose(freq, p, atol=0.03)
        accept = float(np.mean(toks == d))
        assert accept == pytest.approx(p[d], abs=0.03)
        # rejected draws are the residual: p conditioned on != d
        rej = toks[toks != d]
        resid = p.copy()
        resid[d] = 0
        resid /= resid.sum()
        freq = np.bincount(rej, minlength=v) / len(rej)
        np.testing.assert_allclose(freq, resid, atol=0.03)


# ---------------------------------------------------------------------------
# bugfix sweep: draft-proposer slot recycling, budget overshoot
# ---------------------------------------------------------------------------


class TestDraftProposerRecycledSlot:
    def test_longer_history_in_recycled_slot_rewinds(self, params):
        """A recycled slot whose new request has a *longer* history than
        the stale cursor must re-prefill from the divergence point, not
        catch up from another request's KV.  (The old guard only reset
        on ``_pos > len(h)``, so this exact shape proposed from stale
        rows whenever ``free_slot`` was missed — e.g. a proposer reused
        across engines.)"""
        prompts = make_prompts(seed=5, lens=(6, 14))
        stale = DraftModelProposer(params, CFG, batch_slots=1, max_len=32)
        stale.propose_batch([(0, list(prompts[0]), 3)])
        # no free_slot: slot 0 now holds prompts[0]'s KV, cursor 6
        got = stale.propose_batch([(0, list(prompts[1]), 3)])
        fresh = DraftModelProposer(params, CFG, batch_slots=1, max_len=32)
        want = fresh.propose_batch([(0, list(prompts[1]), 3)])
        assert got == want

    def test_shared_prefix_rewinds_to_divergence(self, params):
        """Divergence mid-history: only the suffix past the longest
        common prefix re-prefills, and drafts still match a fresh
        proposer's."""
        base = make_prompts(seed=6, lens=(10,))[0]
        h1 = base[:8] + [7, 7]
        h2 = base[:8] + [9, 9, 9, 9]
        prop = DraftModelProposer(params, CFG, batch_slots=1, max_len=32)
        prop.propose_batch([(0, list(h1), 3)])
        got = prop.propose_batch([(0, list(h2), 3)])
        fresh = DraftModelProposer(params, CFG, batch_slots=1, max_len=32)
        want = fresh.propose_batch([(0, list(h2), 3)])
        assert got == want

    def test_engine_recycles_slot_to_longer_request(self, params):
        """The ISSUE scenario end to end: short request finishes, a
        longer request lands in the same slot.  With draft == target
        every greedy draft must be accepted — stale draft KV would show
        up here as a collapsed acceptance rate."""
        prompts = make_prompts(seed=7, lens=(3, 12))
        prop = DraftModelProposer(params, CFG, batch_slots=1, max_len=32)
        eng = ContinuousBatcher(
            params, CFG, batch_slots=1, max_len=32, chunk_size=16,
            spec=SpecConfig(prop, k=3),
        )
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=list(p), max_new_tokens=6))
        eng.run()
        summ = eng.stats_summary()
        assert summ["draft_tokens"] > 0
        assert summ["acceptance_rate"] == 1.0


class TestBudgetOvershoot:
    def test_decode_batch_plus_starvation_guard(self, params):
        """token_budget=1 with a full decode batch and a queued prefill:
        the step schedules one decode baseline per busy slot
        (unconditional) + 1 starvation-guard prefill token — overshoot =
        scheduled - 1, reported, not hidden."""
        eng = ContinuousBatcher(params, CFG, batch_slots=3, max_len=32,
                                chunk_size=16, token_budget=1)
        for i in range(2):
            eng.submit(Request(uid=i, prompt=[1 + i, 2, 3],
                               max_new_tokens=12))
        # drive both requests past prefill into decode (admission and
        # prefill both happen inside step(); budget=1 prefills serially),
        # leaving the third slot free for the incoming prompt
        while any(s.prefilling for s in eng.slots) or eng.steps == 0:
            eng.step()
        eng.submit(Request(uid=9, prompt=list(range(1, 11)),
                           max_new_tokens=4))
        eng.step()  # 2 decode baselines + 1 guarded prefill token
        st = eng.step_stats[-1]
        assert st.decode_tokens == 2
        assert st.prefill_tokens == 1  # starvation guard
        assert st.scheduled_tokens == 3
        assert st.budget_overshoot == 2
        summ = eng.stats_summary()
        assert summ["max_budget_overshoot"] >= 2.0
        assert summ["budget_overshoot_tokens"] >= 2.0

    def test_no_budget_no_overshoot(self, params):
        eng = ContinuousBatcher(params, CFG, batch_slots=2, max_len=32)
        eng.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=4))
        eng.run()
        assert all(s.budget_overshoot == 0 for s in eng.step_stats)

    def test_within_budget_no_overshoot(self, params):
        eng = ContinuousBatcher(params, CFG, batch_slots=2, max_len=32,
                                chunk_size=4, token_budget=16)
        eng.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=4))
        eng.submit(Request(uid=1, prompt=[4, 5], max_new_tokens=4))
        eng.run()
        assert all(s.budget_overshoot == 0 for s in eng.step_stats)
