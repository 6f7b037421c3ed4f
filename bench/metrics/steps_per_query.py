"""Mean engine loop iterations per real request (``SearchStats.n_steps``)."""


def read(run):
    q = run.counters.get("compass_queries_total", 0.0)
    return run.counters.get("compass_steps_total", 0.0) / q if q else None
