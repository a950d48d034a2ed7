"""Every committed limit of ``correct`` lies between the readings it was
set from (``bench/readings/<workload>.json``, read on the chip at the
cell's own size by ``bench/control.py``): above every sound run of the
program, and low enough that the control, and each fault the cell can
have, reads beyond the limit of at least one number."""
import json
import os

import pytest

from bench import common

READINGS = os.path.join(common.BENCH_DIR, "readings")
WORKLOADS = [w["name"] for w in common.benchmark()["workloads"]]


def _readings(workload):
    with open(os.path.join(READINGS, workload + ".json")) as f:
        return json.load(f)


def _fails(readings: dict, limits: dict) -> bool:
    """Whether the readings of one control or fault (a list of seeds per
    number) exceed some number's limit on every seed."""
    return any(min(readings[k]) > lim for k, lim in limits.items() if k in readings)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_limits_between_readings(workload):
    limits = common.cell(workload)[2]["check"]["limits"]
    r = _readings(workload)
    for k, lim in limits.items():
        assert max(r["program"][k]) < lim, (k, max(r["program"][k]), lim)
        assert len(r["program"][k]) >= 3
    assert _fails(r["control"], limits), r["control"]
    for name, fault in r.get("faults", {}).items():
        assert _fails(fault, limits), (name, fault)
