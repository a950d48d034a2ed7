"""Tail readers over a hand-made run: a request the run stopped waiting
for counts at the time it waited, so a tail is always a number."""
import math

from bench import common


def _run(requests, wait_end):
    run = common.Run(workload="w", kind="serve_open", chips=1, cfg=None, traffic={},
                     peaks={})
    run.requests = requests
    run.extra["wait_end"] = wait_end
    return run


def _req(due, first=None, last=None, n_recv=0, admitted=None):
    return {"due_at": due, "first": first, "last": last, "n_recv": n_recv,
            "admitted": admitted}


def test_unserved_request_counts_at_its_wait():
    reqs = [_req(0.0, 1.0, 2.0, 11, 0.5) for _ in range(19)] + [_req(10.0)]
    run = _run(reqs, wait_end=40.0)
    ttft = common.metric_reader("ttft_p95_ms")(run)
    wait = common.metric_reader("queue_wait_p95_ms.chat")(run)
    assert math.isfinite(ttft) and math.isfinite(wait)
    # p95 of 19 x 1 s and one 30 s wait: 5% of the way from 1 s to 30 s
    assert abs(ttft - (1000.0 + 0.05 * 29000.0)) < 1e-6
    assert abs(wait - (500.0 + 0.05 * 29500.0)) < 1e-6


def test_tpot_counts_streams_cut_short():
    reqs = [_req(0.0, 1.0, 2.0, 11) for _ in range(19)] + [_req(0.0, 1.0, 5.0, 5)]
    tpot = common.metric_reader("tpot_p95_ms")(_run(reqs, wait_end=9.0))
    assert abs(tpot - (100.0 + 0.05 * 900.0)) < 1e-6
