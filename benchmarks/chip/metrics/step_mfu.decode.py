"""step_mfu.decode: model FLOPs of the tokens the window's engine calls
computed over those calls' host seconds times the chip's peak, in %.
See ``chipbench.readers``."""

from chipbench.readers import step_mfu


def read(run):
    return step_mfu(run)
