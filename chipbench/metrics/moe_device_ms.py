"""Device time a traced step of the sparse-expert layers' routed path
(router, top-k, sort, dispatch, the grouped products over the experts
held, combine: everything under ``dl4j_moe``), forward, rematerialised
and backward; the shared expert is the layer's own and not in it."""

from chipbench import xingmarks as xm


def read(ctx):
    return xm.ms_or_none(ctx, xm.in_moe)
