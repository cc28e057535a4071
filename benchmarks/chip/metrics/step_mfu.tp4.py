"""step_mfu.tp4: model FLOPs of the tokens the window's engine calls
computed over those calls' host seconds times the peak of the cell's four
chips, in %.  See ``chipbench.readers``."""

from chipbench.readers import step_mfu


def read(run):
    return step_mfu(run)
