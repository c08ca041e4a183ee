"""The attention core against the MXU's peak: the FLOPs the causal half
requires (``model.attention_flops``, forward, a layer application; three
times that a step for forward and backward; rematerialised work is not
required work) over the peak, over the core's device time a step
(``attn_device_ms``). Bound: compute at these shapes (the core moves 64
MB a layer application against 69 GFLOP). The same work whatever
implements it: XLA's ``dot``s or a kernel."""

from chipbench import loopmarks as lm


def read(ctx):
    ms = lm.marked_ms(ctx, lm.in_attention)
    flops = getattr(ctx.model, "attention_flops", None)
    if not ms or flops is None:
        return None
    required = 3.0 * flops(ctx.cfg) * ctx.model.layer_applications(ctx.cfg) \
        * ctx.result["batch"] / ctx.result["chips"]
    return 100.0 * required / ctx.peak["flops_per_s"] / (ms * 1e-3)
