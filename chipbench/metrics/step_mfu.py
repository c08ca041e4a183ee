"""The whole step's share of the chip's peak: FLOPs the forward and
backward passes require for the traced steps (3 x forward, nothing
recomputed counted) over traced seconds x chips x peak."""


def read(ctx):
    red = ctx.reduced
    if red is None or not red.steps:
        return None
    flops = 3.0 * ctx.model.flops_per_sample(ctx.cfg) \
        * ctx.result["batch"] * red.steps
    return 100.0 * flops / (red.window_s * ctx.result["chips"]
                            * ctx.peak["flops_per_s"])
