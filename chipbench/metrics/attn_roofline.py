"""The causal attention core against the MXU's peak: the FLOPs the causal
half requires (``model.core_flops``, forward, a sequence and an
application of the core; ``model.attention_applications`` applications a
sequence; three times that a step for forward and backward;
rematerialised work is not required work) over the peak, over the core's
device time a step (``attn_device_ms``). Bound: compute at these shapes
(the looped model's core moves 64 MB a layer application against 69
GFLOP). The same work whatever implements it: XLA's ``dot``s or a
kernel. Every language model's ``model.py`` names its count so: the
looped model's ``2 S^2 H D`` x 24, the latent attention's
``S^2 H (192 + 128)`` x 6, the grouped-query core's ``2 S^2 H D`` x 1."""

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
