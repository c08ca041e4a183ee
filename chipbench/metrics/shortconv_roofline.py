"""The gated short-convolution mixers against the MXU's peak: the FLOPs
their projections and taps require (``model.shortconv_flops`` a token a
mixer, forward; three times that a step; rematerialised work is not
required work) over the peak, over the mixers' device time a step
(``shortconv_device_ms``). Bound: compute, by the two projections; the
share says how far the memory-bound gate / convolution / gate chain
between them holds the mixer from the projections' own."""

from chipbench import lfm2marks as fm
from chipbench import xingmarks as xm


def read(ctx):
    ms = xm.ms_or_none(ctx, fm.in_shortconv)
    flops = getattr(ctx.model, "shortconv_flops", None)
    if ms is None or flops is None:
        return None
    tokens = ctx.result["batch"] * ctx.cfg["seq_len"] / ctx.result["chips"]
    required = 3.0 * flops(ctx.cfg) * ctx.model.shortconv_mixers(ctx.cfg) \
        * tokens
    return 100.0 * required / ctx.peak["flops_per_s"] / (ms * 1e-3)
