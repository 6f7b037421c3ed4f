"""Host-clock seconds of ``quantize_index`` (codebook training and
encoding) in the run's set-up."""


def read(run):
    return run.phases.get("quantize_index")
