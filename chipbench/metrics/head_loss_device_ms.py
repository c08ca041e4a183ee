"""Device time a traced step in the heads, exit gates and the
exit-weighted loss of a looped model's passes (``dl4j_head_loss``),
forward, rematerialised and backward."""

from chipbench import loopmarks as lm


def read(ctx):
    return lm.marked_ms(ctx, lm.in_heads)
