"""Mean host-clock time of one micro-batch in the service's dispatch, from
its ``BucketStats`` (dispatch to ``block_until_ready``) over the traced
window."""


def read(run):
    b = run.buckets
    return 1000.0 * b["total_exec_s"] / b["n_batches"] if b["n_batches"] else None
