"""CPU rehearsal of a cell: the cell's whole path, end to end, at the
configuration's and the mix's ``rehearsal`` sizes, with the Pallas paged
kernel in interpret mode.  Its output is not a measurement: it checks
paths, arguments and control flow, and prints the result line under a
heading that says so.

    python bench/rehearse.py --workload <cell> --seed 7 --seconds 3 --trace 0
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

import functools  # noqa: E402
import json  # noqa: E402

from bench import run  # noqa: E402


def interpret_paged_kernel():
    """Off the TPU the program takes its XLA lowering of paged attention;
    the rehearsal runs the Pallas kernel itself, interpreted."""
    from repro.kernels import flash_attention, ops

    ops._paged_xla = functools.partial(flash_attention.paged_flash_attention,
                                       interpret=True)


def main(argv=None):
    interpret_paged_kernel()
    args = run.parse(argv)
    result = run.execute(args, rehearsal=True)
    print("REHEARSAL on the CPU at smoke sizes -- not a measurement:")
    print(json.dumps(result))


if __name__ == "__main__":
    main()
