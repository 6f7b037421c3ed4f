"""Device ms per traced micro-batch in the fused visit-step kernel
(``compass/visit_step``, ``kernels/visit_step.py`` over
``kernels/row_gather.py``)."""
from bench import scopes


def read(run):
    return scopes.stage_ms(run, "compass/visit_step")
