"""Iterations the batched engine loop ran per traced micro-batch: it runs
until its slowest lane is done, so read next to ``steps_per_query`` (the
mean over real lanes) it shows how far the lanes of a batch diverge.
Counted on the device trace as the events of an instruction directly in
the loop's body, per ``compass/serve_batch`` span."""
from bench import scopes


def read(run):
    att = scopes.stages(run)
    if att is None or not any(att.loop_iterations):
        return None
    return sum(att.loop_iterations) / len(att.loop_iterations)
