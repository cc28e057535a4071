"""codebook_matmul_roofline.tp4: the codebook kernel's share of its
roofline in the traced window, in %, from the first device's ops: each
call at the per-shard shapes its HLO text states, against one chip's
peaks.  See ``chipbench.readers``."""

from chipbench.readers import codebook_roofline


def read(run):
    return codebook_roofline(run)
