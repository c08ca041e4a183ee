"""Everything that is not a convolution, a matmul or a collective against
HBM's peak: the bytes those operations move to and from HBM as compiled
(each one's outputs and operands once, read from the instruction's text in
the trace by ``chipbench.trace.hbm_bytes``) over 819 GB/s, over their summed
device time on the busiest device. Bound: memory. These are BatchNorm's
passes, activations, pooling, residual adds and the updater, whether an XLA
loop fusion or a Pallas call runs them; what the compiler folded into a
convolution's fusion is in ``conv_roofline``'s time instead."""


def read(ctx):
    red = ctx.reduced
    if red is None:
        return None
    dev = red.busiest()
    seconds, nbytes = dev.seconds("other"), dev.bytes("other")
    if seconds <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / ctx.peak["hbm_bytes_per_s"] / seconds
