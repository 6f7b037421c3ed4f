"""Host-clock seconds of the index build (``build_index``) in the run's
set-up."""


def read(run):
    return run.phases["build_index"]
