"""Device time a traced step of forward work run a second time in the
backward pass: the ops JAX names ``rematted_computation`` (the looped
stack's stretches and its attention core; in the sparse and hybrid
decoders the stretches ``rematerializeStack()`` marks: hyper-connections,
routed path, mixers, attention core). What rematerialisation costs in
time for the memory it saves; ``step_mfu`` does not count it as required.
The map gives these ops the backward phase, since they run in the
backward pass, so this time is also part of ``bwd_device_ms``."""

from chipbench import loopmarks as lm


def read(ctx):
    return lm.marked_ms(ctx, lm.is_remat)
