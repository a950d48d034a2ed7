"""The control fails the check: the plain reference in the precision
below the configuration's, put in the program's place, reads beyond each
cell's limit (here at the rehearsal sizes, with their limits)."""
import functools

import pytest

from bench import common, control


@pytest.fixture(autouse=True)
def rehearsal(monkeypatch):
    from repro.kernels import flash_attention, ops

    monkeypatch.setattr(ops, "_paged_xla", functools.partial(
        flash_attention.paged_flash_attention, interpret=True))


def _args(workload):
    return control.argparse.Namespace(workload=workload, seconds=2.0, rehearsal=True)


@pytest.mark.parametrize("workload", ["internlm2_1_8b-chat", "internlm2_1_8b-docs"])
def test_serving_control_fails(workload):
    limit = common.cell(workload)[2]["rehearsal"]["check"]["limits"]["gap_std"]
    out = control.serve_readings(_args(workload), seed=7, control=True)
    assert out["gap_std"] <= limit < out["control_gap_std"]


@pytest.mark.usefixtures("pending_cells")
def test_training_control_and_faults_fail():
    limits = common.cell("bert_large-dp4")[2]["rehearsal"]["check"]["limits"]
    out = control.train_readings(_args("bert_large-dp4"), seed=7, control=True)
    for name in ("control_fp8", "fault_half_batch", "fault_no_exchange"):
        assert any(out[name][k] > lim for k, lim in limits.items()), (name, out[name])
