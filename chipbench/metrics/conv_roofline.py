"""Convolutions and matmuls against the MXU's peak: FLOPs every
convolution and dense layer requires for the traced steps (3 x forward)
over peak, over the summed device time of the events that implement them
(convolution and matmul ops and the fusions that hold one) on the busiest
device. Bound: compute. The same work whatever implements it; where the
trace shows fewer such events a step than the configuration has matmuls to
run (forward, input gradient and weight gradient of each, less the first
layer's input gradient), the work went somewhere this reader cannot see
and it reads nothing."""


def read(ctx):
    red = ctx.reduced
    if red is None or not red.steps:
        return None
    dev = red.busiest()
    seconds = dev.seconds("conv")
    events = len(dev.intervals("conv")) / red.steps
    if seconds <= 0 or events < 3 * ctx.model.n_matmuls(ctx.cfg) - 1:
        return None
    flops = 3.0 * ctx.model.flops_per_sample(ctx.cfg) \
        * ctx.result["batch"] / ctx.result["chips"] * red.steps
    return 100.0 * flops / ctx.peak["flops_per_s"] / seconds
