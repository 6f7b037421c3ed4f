"""Share of the traced window in which no operation ran on the device."""
from bench import tracing


def read(run):
    if run.trace is None:
        return None
    busy = tracing.busy_s(run.trace)
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / run.trace.window_s)
