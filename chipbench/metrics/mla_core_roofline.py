"""The latent attention's core against the MXU's peak: the FLOPs the
causal half requires (``model.core_flops``: ``S^2 * H * (192 + 128)`` a
layer application, forward; three times that a step; rematerialised work
is not required work) over the peak, over the core's device time a step
(``mla_core_device_ms``). Bound: compute. The same work whatever
implements it."""

from chipbench import loopmarks as lm
from chipbench import xingmarks as xm


def read(ctx):
    ms = xm.ms_or_none(ctx, lm.in_attention)
    flops = getattr(ctx.model, "core_flops", None)
    if ms is None or flops is None:
        return None
    required = 3.0 * flops(ctx.cfg) * ctx.model.attention_applications(
        ctx.cfg) * ctx.result["batch"] / ctx.result["chips"]
    return 100.0 * required / ctx.peak["flops_per_s"] / (ms * 1e-3)
