"""Mean rows scored from PQ codes per real request (``SearchStats.n_adc``)."""


def read(run):
    q = run.counters.get("compass_queries_total", 0.0)
    return run.counters.get("compass_adc_total", 0.0) / q if q else None
