"""collective_share.tp4: the seconds in which an all-reduce, all-gather,
reduce-scatter, collective-permute or all-to-all ran on the first device
(an async pair's halves counted once) over that device's busy seconds in
the traced window, in %.  See ``chipbench.readers``."""

from chipbench.readers import collective_share


def read(run):
    return collective_share(run)
