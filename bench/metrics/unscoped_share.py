"""Share of the device's busy time in operations that no stage scope places
(own time of the ``unscoped`` operations over busy time): what the stage
metrics cannot see."""
from bench import scopes


def read(run):
    att = scopes.stages(run)
    if att is None or not att.busy_s:
        return None
    return 100.0 * att.stage_s.get(scopes.UNSCOPED, 0.0) / att.busy_s
