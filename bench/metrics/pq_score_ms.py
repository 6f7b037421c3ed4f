"""Device ms per traced micro-batch in the ADC scoring kernel of the
quantized tier's stage one (``compass/pq_score``, ``kernels/pq_score.py``)."""
from bench import scopes


def read(run):
    return scopes.stage_ms(run, "compass/pq_score")
