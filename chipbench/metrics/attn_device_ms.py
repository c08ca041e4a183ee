"""Device time a traced step of the attention core's ops (scores, mask,
softmax, weighted sum: ``dl4j_attn_core``), forward, rematerialised and
backward, whatever implements them."""

from chipbench import loopmarks as lm


def read(ctx):
    return lm.marked_ms(ctx, lm.in_attention)
