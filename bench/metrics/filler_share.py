"""Share of micro-batch lanes that carried a filler, not a request."""


def read(run):
    b = run.buckets
    lanes = b["n_batches"] * run.batch_size
    return 100.0 * b["n_fillers"] / lanes if lanes else None
