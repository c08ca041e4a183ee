"""Device time a traced step under the looped stack's scopes: every op
the step-program map gives a pass of the loop (``dl4j_ut<t>``) and not a
head, forward, rematerialised and backward alike (a weight-gradient
product fused with Adam's update counts with its product)."""

from chipbench import loopmarks as lm


def read(ctx):
    return lm.marked_ms(ctx, lm.in_stack)
