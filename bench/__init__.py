"""On-chip benchmark of the serving engine and the DropCompute trainer.

Entry point: ``python bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``; the cells are listed in ``BENCHMARK.json``
at the repository root.
"""
