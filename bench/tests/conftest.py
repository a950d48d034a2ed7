"""The benchmark's own tests, run by explicit path on the CPU:

    python -m pytest bench/tests

The cells run at their configurations' and mixes' ``rehearsal`` sizes,
with the Pallas paged kernel interpreted; nothing here is a measurement.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import pytest  # noqa: E402


@pytest.fixture
def pending_cells(monkeypatch):
    """``BENCHMARK.json`` with the cells of ``bench/pending/`` added: cells
    whose harness is in place but which have not run on the chip yet."""
    import glob
    import json

    from bench import common

    bench = common.benchmark()
    for path in sorted(glob.glob(os.path.join(common.BENCH_DIR, "pending", "*.json"))):
        with open(path) as f:
            extra = json.load(f)
        for k in ("configs", "workloads", "end_to_end", "per_layer"):
            bench[k] = bench[k] + extra.get(k, [])
    monkeypatch.setattr(common, "benchmark", lambda: bench)
