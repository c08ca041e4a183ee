"""Device time a traced step of the causal attention core's ops (scores,
mask, softmax, weighted sum: ``dl4j_attn_core``), forward, rematerialised
and backward, whatever implements them: the looped model's 16 x 128
heads, the latent attention's 192-wide query/key and 128-wide value
heads, the grouped-query core's 32 query heads over 8 key/value heads of
64. A step with no ``attn_core`` op gives no reading, not 0
(``xingmarks.ms_or_none``, the rule the sparse and hybrid cells' readers
kept before they were folded into this one)."""

from chipbench import loopmarks as lm
from chipbench import xingmarks as xm


def read(ctx):
    return xm.ms_or_none(ctx, lm.in_attention)
