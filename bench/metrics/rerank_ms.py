"""Device ms per traced micro-batch in the quantized tier's stage two, the
exact rerank of the stage-one survivors, with the kernel it calls to score
them (``compass/quant/rerank`` over ``compass/filter_distance``,
``core/quant/rerank.py``)."""
from bench import scopes


def read(run):
    return scopes.within_ms(run, "compass/quant/rerank")
