"""setup_s: process start to window open (weights made on the chip,
engine built, the cell's shapes compiled or loaded from the compile
cache, a closed loop's slots filled).  Host clock."""


def read(run):
    return run.setup_s
