"""Mean full-precision rows scored per real request (``SearchStats.n_dist``)."""


def read(run):
    q = run.counters.get("compass_queries_total", 0.0)
    return run.counters.get("compass_dist_total", 0.0) / q if q else None
