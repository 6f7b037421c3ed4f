"""Share of real requests that the planner ran as PREFILTER."""


def read(run):
    b = run.buckets
    return 100.0 * b["n_mode_prefilter"] / b["n_requests"] if b["n_requests"] else None
