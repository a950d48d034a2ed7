"""The generator: every seed gets the same sizes and gaps, in its own order."""
from collections import Counter

import numpy as np

from bench import common, traffic


def _chat():
    return common.cell("internlm2_1_8b-chat")[2]


def test_same_work_for_every_seed():
    mix = {k: v for k, v in _chat().items() if k != "order_seed"}
    a = traffic.requests(mix, 1, 30.0, 92544)
    b = traffic.requests(mix, 2**31 + 12345, 30.0, 92544)
    assert len(a) == len(b) == round(mix["rate_rps"] * 30)
    for f in (lambda r: len(r.prompt), lambda r: r.max_new, lambda r: r.group,
              lambda r: r.greedy):
        assert sorted(map(f, a)) == sorted(map(f, b))
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    # each schedule leaves out one gap of the same multiset: its last
    gaps = lambda rs: np.round(np.diff([r.due for r in rs]), 9).tolist()  # noqa: E731
    shared = sum((Counter(gaps(a)) & Counter(gaps(b))).values())
    assert shared >= len(a) - 2


def test_order_seed_fixes_the_schedule():
    mix = {**_chat(), "order_seed": 17}
    a = traffic.requests(mix, 1, 30.0, 92544)
    b = traffic.requests(mix, 2**31 + 12345, 30.0, 92544)
    for f in (lambda r: r.due, lambda r: len(r.prompt), lambda r: r.max_new,
              lambda r: r.group, lambda r: r.greedy):
        assert list(map(f, a)) == list(map(f, b))
    assert [r.prompt for r in a] != [r.prompt for r in b]


def test_open_loop_schedule_and_determinism():
    mix = _chat()
    a = traffic.requests(mix, 5, 20.0, 1000)
    assert a[0].due == 0.0 and all(0 <= r.due < 20.0 for r in a)
    assert all(x.due <= y.due for x, y in zip(a, a[1:]))
    again = traffic.requests(mix, 5, 20.0, 1000)
    assert [r.prompt for r in a] == [r.prompt for r in again]
    sp = mix["shared_prefix"]
    for r in a:
        assert sp["tokens"] + mix["prompt"]["min"] <= len(r.prompt) <= sp["tokens"] + mix["prompt"]["max"]
        assert mix["output"]["min"] <= r.max_new <= mix["output"]["max"]
    same = [r for r in a if r.group == a[0].group]
    assert all(r.prompt[: sp["tokens"]] == a[0].prompt[: sp["tokens"]] for r in same)
    assert sum(r.greedy for r in a) == len(a) // mix["sampling"]["greedy_every"]


def test_queued_mix_is_due_at_once():
    mix = common.cell("internlm2_1_8b-docs")[2]
    reqs = traffic.requests(mix, 3, 30.0, 1000)
    assert len(reqs) == mix["queue"] and all(r.due == 0.0 and r.greedy for r in reqs)
