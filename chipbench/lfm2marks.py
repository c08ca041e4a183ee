"""What the readers of the hybrid decoder's per-layer metrics add to
``chipbench.xingmarks``: the part the step-program map gives an op of a
gated short-convolution mixer (``shortconv``: both projections, the gates
and the taps, everything under ``dl4j_shortconv``). A program without the
mark gives no such entry."""

from chipbench import loopmarks as lm


def in_shortconv(entry) -> bool:
    return entry[lm.PART] == "shortconv"
