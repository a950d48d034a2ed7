"""Engine spans: every step's phases cover its wall time, the spans
change no token, and admission says why it stopped.

``ContinuousBatcher.step`` times each host phase into
``StepStats.phases`` and marks it ``engine:<phase>`` on the profiler's
clock (``repro.serve.spans``); ``StepStats.admit_blocked`` records why
admission stopped with a request still queued.
"""
import gc

import jax
import numpy as np
import pytest

from repro.models import ModelConfig
from repro.models.model import init_params
from repro.serve import ContinuousBatcher, Request, SamplingParams
from repro.serve.spans import span

CFG = ModelConfig(
    name="serve-spans-t", n_layers=4, d_model=512, n_heads=4, n_kv_heads=2, d_ff=2048,
    vocab_size=4096, layer_pattern="LG", sliding_window=6, dtype="float32", remat=False,
)

PHASES = {"admit", "share", "propose", "schedule", "kv_prepare", "pack",
          "dispatch", "sync", "sync_overflow", "emit"}

ENGINES = {
    "paged": dict(packed=True, cache="paged", page_size=4, token_budget=8),
    "dense": dict(token_budget=8),
}

# Outputs of these engines before the spans existed (greedy even uids,
# seeded top-p sampling odd ones): the spans must change no token.
BEFORE = [[3830, 1920, 3652, 864, 582, 2973], [52, 1404, 1588, 1784, 409, 3763],
          [3408, 1203, 3185, 606, 1878, 1974], [2235, 1478, 3937, 3060, 590, 323],
          [684, 3333, 2725, 1690, 4046, 1215], [2724, 2923, 2102, 2371, 4095, 2120]]


@pytest.fixture(scope="module")
def params():
    p = init_params(jax.random.PRNGKey(0), CFG)
    # layers strong enough against the embedding that the tokens vary
    p["stack"] = jax.tree_util.tree_map(lambda x: 8.0 * x, p["stack"])
    return p


def prompts(seed=0, lens=(3, 5, 12, 4, 8, 6)):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, size=n).tolist() for n in lens]


def sampling(i):
    if i % 2 == 0:
        return SamplingParams()
    return SamplingParams(temperature=0.8, top_p=0.9, seed=i)


@pytest.mark.parametrize("layout", sorted(ENGINES))
def test_phases_cover_each_step_and_tokens_are_unchanged(params, layout):
    eng = ContinuousBatcher(params, CFG, batch_slots=2, max_len=24, chunk_size=4,
                            **ENGINES[layout])
    for i, p in enumerate(prompts()):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=6, sampling=sampling(i)))
    # a collection pause may land between two phases; the steps of this
    # small model are short enough for one to pass 5% of a step
    gc.disable()
    try:
        eng.run()
    finally:
        gc.enable()
    assert [eng.finished[i].output for i in range(len(BEFORE))] == BEFORE
    assert eng.step_stats
    for st in eng.step_stats:
        assert set(st.phases) == PHASES
        covered = sum(st.phases.values())
        assert covered <= st.wall_time
        assert covered >= 0.95 * st.wall_time, (st.step, st.phases, st.wall_time)
        assert st.sync_time == st.phases["sync"] + st.phases["sync_overflow"]
    starts = [st.started_at for st in eng.step_stats]
    assert starts == sorted(starts)
    kinds = {st.kind for st in eng.step_stats}
    assert kinds == {"decode", "mixed"}
    for st in eng.step_stats:
        if st.kind == "mixed":
            assert st.prefill_tokens + st.draft_tokens > 0
    s = eng.stats_summary()
    assert s["mean_step_wall"] == pytest.approx(
        np.mean([st.wall_time for st in eng.step_stats]))
    assert s["mean_step_sync"] == pytest.approx(
        s["mean_phase_sync"] + s["mean_phase_sync_overflow"])
    assert {k[len("mean_phase_"):] for k in s if k.startswith("mean_phase_")} == PHASES


def test_decode_kind_runs_the_decode_program(params):
    """A step is "decode" exactly when every grant is one token: the
    packed engine then runs its decode-capacity program."""
    eng = ContinuousBatcher(params, CFG, batch_slots=2, max_len=24, chunk_size=4,
                            packed=True, cache="paged", page_size=4)
    seen = []
    run_packed = eng._run_packed

    def spy(grants, out_base):
        seen.append(all(len(t) == 1 for _, _, t in grants))
        return run_packed(grants, out_base)

    eng._run_packed = spy
    for i, p in enumerate(prompts(lens=(9, 2))):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=5))
    eng.run()
    assert [st.kind == "decode" for st in eng.step_stats] == seen
    assert any(seen) and not all(seen)


def _first_step(eng, reqs):
    for r in reqs:
        eng.submit(r)
    eng.step()
    return eng.step_stats[0]


def test_admission_reasons(params):
    rng = np.random.default_rng(9)
    p = rng.integers(0, CFG.vocab_size, size=12).tolist()
    # nothing left waiting
    eng = ContinuousBatcher(params, CFG, batch_slots=2, max_len=24)
    assert _first_step(eng, [Request(0, list(p), 4)]).admit_blocked is None
    # one slot, two requests: the second waits for a slot
    eng = ContinuousBatcher(params, CFG, batch_slots=1, max_len=24)
    st = _first_step(eng, [Request(0, list(p), 4), Request(1, list(p[::-1]), 4)])
    assert st.admit_blocked == "slots" and st.queued_requests == 2
    # a pool of a few pages: a slot is free but the pool cannot reserve
    # the second request's worst case (16 tokens = 4 pages of 4)
    eng = ContinuousBatcher(params, CFG, batch_slots=2, max_len=24, cache="paged",
                            page_size=4, num_pages=6)
    st = _first_step(eng, [Request(0, list(p), 4), Request(1, list(p[::-1]), 4)])
    assert st.admit_blocked == "pool" and eng.slots[1].free
    # an identical prompt is parked behind the in-flight prefix
    q = rng.integers(0, CFG.vocab_size, size=48).tolist()
    eng = ContinuousBatcher(params, CFG, batch_slots=2, max_len=64, chunk_size=16,
                            cache="paged", page_size=16)
    st = _first_step(eng, [Request(0, list(q), 4), Request(1, list(q), 4)])
    assert st.admit_blocked == "prefix" and eng.slots[1].free
    eng.run()
    reasons = [s.admit_blocked for s in eng.step_stats]
    assert reasons[-1] is None and "prefix" in reasons


def test_span_times_into_phases_and_annotates():
    phases = {}
    with span("engine:admit", phases) as ann:
        assert isinstance(ann, jax.profiler.TraceAnnotation)
    with span("engine:admit", phases):
        pass
    with span("frontend:feed"):
        pass
    assert set(phases) == {"admit"} and phases["admit"] > 0
