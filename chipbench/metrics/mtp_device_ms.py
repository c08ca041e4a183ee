"""Device time a traced step of the multi-token-prediction module's own
layers (the joining projection, its expert layer under hyper-connections,
its sum-out and norm: nodes named ``mtp_*``), forward, rematerialised and
backward; its share of the shared head and loss is ``dl4j_head_loss``'s
and not in it."""

from chipbench import xingmarks as xm


def read(ctx):
    return xm.ms_or_none(ctx, xm.in_mtp)
