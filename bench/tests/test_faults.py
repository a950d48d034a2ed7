"""A run with its timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip (the cell runs at its
rehearsal size on the CPU), plants one fault the cell can have, drives
the rest of the run, and reads ``correct``.  The sound run is there to
show the faults are what flips it.
"""
import functools

import jax.numpy as jnp
import pytest

from bench import run


@pytest.fixture(autouse=True)
def interpreted_kernel(monkeypatch):
    from repro.kernels import flash_attention, ops

    monkeypatch.setattr(ops, "_paged_xla", functools.partial(
        flash_attention.paged_flash_attention, interpret=True))


def _correct(workload, seed=5, seconds=2.0):
    args = run.parse(["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", "0"])
    return run.execute(args, rehearsal=True)["correct"]


@pytest.mark.parametrize("workload", ["internlm2_1_8b-chat", "internlm2_1_8b-docs"])
def test_altered_token(workload, monkeypatch):
    from repro.serve import scheduler

    assert _correct(workload)
    sample = scheduler.sample_tokens

    def altered(logits, *a):
        return (sample(logits, *a) + 1) % logits.shape[-1]

    monkeypatch.setattr(scheduler, "sample_tokens", altered)
    assert not _correct(workload)


def _plant(monkeypatch, fault):
    from repro.launch import steps

    make = steps.make_train_step

    def broken(cfg, shape, drop, n_workers=None, **kw):
        opt, step = make(cfg, shape, drop, n_workers, **kw)
        rows = shape.global_batch

        def faulty(params, opt_state, batch, lat):
            if fault == "unchanged":
                return params, opt_state, step(params, opt_state, batch, lat)[2]
            keep = rows // 2 if fault == "half_batch" else rows // n_workers
            w = batch["weights"] * (jnp.arange(rows) < keep)[:, None]
            return step(params, opt_state, {**batch, "weights": w}, lat)

        return opt, faulty

    monkeypatch.setattr(steps, "make_train_step", broken)


@pytest.mark.usefixtures("pending_cells")
def test_training_sound():
    assert _correct("bert_large-dp4")


@pytest.mark.usefixtures("pending_cells")
@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "no_exchange"])
def test_training_fault(fault, monkeypatch):
    """``no_exchange`` keeps chip 0's rows alone: what a step that skips
    the all-reduce applies when each chip updates from its own gradient."""
    _plant(monkeypatch, fault)
    assert not _correct("bert_large-dp4")


@pytest.mark.parametrize("fault", ["sound", "row_repeated", "step_repeated",
                                   "weights", "token_range"])
def test_feed_faults(fault):
    """The training check counts a feed that breaks its guarantees."""
    import numpy as np

    from bench.train_spmd import feed_faults

    r = np.random.default_rng(0)
    batches = [{"tokens": r.integers(0, 50, (8, 16)), "weights": np.ones((8, 16))}
                for _ in range(3)]
    if fault == "row_repeated":
        batches[1]["tokens"][5] = batches[1]["tokens"][1]  # two workers, one row
    elif fault == "step_repeated":
        batches[2] = batches[0]
    elif fault == "weights":
        batches[0]["weights"][3:] = 0.0
    elif fault == "token_range":
        batches[2]["tokens"][0, 0] = 50
    assert (feed_faults(batches, 8, 16, 50) == 0) == (fault == "sound")
