"""Device ms per traced micro-batch in G.NEXT, the graph iterator: the pop,
the neighbour gathers and the one/two-hop choice (``compass/engine/gnext``,
``core/engine/graph_iter.py``)."""
from bench import scopes


def read(run):
    return scopes.stage_ms(run, "compass/engine/gnext")
