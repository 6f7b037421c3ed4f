"""Device ms per traced micro-batch in the queue sorts: ``FixedQueue.merge``,
``FixedQueue.pop`` and ``dedup_new`` (``compass/engine/sort``,
``core/engine/state.py``)."""
from bench import scopes


def read(run):
    return scopes.stage_ms(run, "compass/engine/sort")
