"""Device ms per traced micro-batch in the gather of float32 rows that the
row-gather kernels take from XLA rather than DMA (``compass/row_gather/xla``,
``kernels/row_gather.py``): rows whose width is not a multiple of 128.
Nothing where no served program opens that scope."""
from bench import scopes

SCOPE = "compass/row_gather/xla"


def read(run):
    att = scopes.stages(run)
    if att is None or not any(SCOPE in (ins.op_name or "") for prog in att.programs.values()
                              for ins in prog.instrs.values()):
        return None
    return scopes.within_ms(run, SCOPE)
