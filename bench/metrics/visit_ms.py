"""Device ms per traced micro-batch in the VISIT bookkeeping around the
scoring kernel: the dedup mask, the visited gather and scatter, the
counters (``compass/engine/visit``, ``core/engine/state.py``)."""
from bench import scopes


def read(run):
    return scopes.stage_ms(run, "compass/engine/visit")
