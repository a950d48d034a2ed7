"""Tokens whose gradient the step applied (rows of kept micro-batches
times the sequence length), summed over the window's whole steps, over
the wall time of those steps, the trainer's host loop included."""


def read(run):
    rows = sum(s["kept_rows"] for s in run.steps)
    return rows * run.traffic["seq_len"] / run.window_s
