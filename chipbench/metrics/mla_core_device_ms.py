"""Device time a traced step of the latent attention's core (scores,
mask, softmax, weighted sum over 192-wide query/key and 128-wide value
heads: ``dl4j_attn_core``), forward, rematerialised and backward, whatever
implements it."""

from chipbench import loopmarks as lm
from chipbench import xingmarks as xm


def read(ctx):
    return xm.ms_or_none(ctx, lm.in_attention)
