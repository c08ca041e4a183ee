"""Device time a traced step of forward work run a second time in the
backward pass: the ops JAX names ``rematted_computation`` (the looped
stack's stretches, the attention core, each pass's head). What
rematerialisation costs in time for the memory it saves; ``step_mfu``
does not count it as required."""

from chipbench import loopmarks as lm


def read(ctx):
    return lm.marked_ms(ctx, lm.is_remat)
