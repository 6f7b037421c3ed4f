"""Device ms per traced micro-batch in the whole-state selects of
``state.run_if`` (``compass/engine/select``, ``core/engine/state.py``)."""
from bench import scopes


def read(run):
    return scopes.stage_ms(run, "compass/engine/select")
