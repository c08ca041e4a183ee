"""Device time a traced step in the language-model heads and their loss
(``dl4j_head_loss``: ``blocked_cross_entropy``'s products over the
vocabulary and the row log-sum-exp), forward, rematerialised and
backward: a looped model's four heads with their exit gates and the
exit-weighted loss; the sparse decoder's head and its multi-token-
prediction module's pass over the same ``W``; the hybrid decoder's head,
the embedding's table transposed."""

from chipbench import loopmarks as lm


def read(ctx):
    return lm.marked_ms(ctx, lm.in_heads)
