"""Set-up: process start to window start (imports, device, weights from
the seed, compilation or the compile cache, warm-up)."""


def read(run):
    return run.setup_s
