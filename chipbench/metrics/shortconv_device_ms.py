"""Device time a traced step of the gated short-convolution mixers (the
two projections, the gate / three-tap causal convolution / gate chain
between them: everything under ``dl4j_shortconv``), forward,
rematerialised and backward, whatever implements it."""

from chipbench import lfm2marks as fm
from chipbench import xingmarks as xm


def read(ctx):
    return xm.ms_or_none(ctx, fm.in_shortconv)
