"""Device time a traced step of the hyper-connections (the streams'
norm, the three maps, Sinkhorn's rounds, the read mix and the write mix,
copy-in and sum-out: everything under ``dl4j_mhc``), forward,
rematerialised and backward."""

from chipbench import xingmarks as xm


def read(ctx):
    return xm.ms_or_none(ctx, xm.in_mhc)
