"""Device ms per traced micro-batch in B.NEXT, the relational iterator: the
cluster cursor, the run searches, the fetch and the residual predicate
(``compass/engine/bnext``, ``core/engine/btree_iter.py``)."""
from bench import scopes


def read(run):
    return scopes.stage_ms(run, "compass/engine/bnext")
