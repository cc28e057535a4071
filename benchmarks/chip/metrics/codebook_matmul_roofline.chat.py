"""codebook_matmul_roofline.chat: the codebook kernel's share of its
roofline in the traced window, in %.  See ``chipbench.readers``."""

from chipbench.readers import codebook_roofline


def read(run):
    return codebook_roofline(run)
