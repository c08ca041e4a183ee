"""The program's own Pallas kernels against HBM's peak: the bytes their
``custom-call``s move as compiled (each operand and output once,
``chipbench.trace.hbm_bytes`` on the instruction's text) over the peak,
over their summed device time on the busiest device. Bound: memory. 0
where the step program as compiled holds no kernel of the program's."""

from chipbench import programspans as ps


def read(ctx):
    ops = ps.kernel_ops(ctx.reduced, ps.of(ctx).maps)
    if ops is None:
        return None
    seconds = sum(s for s, _b in ops)
    if seconds <= 0:
        return 0.0      # the step program ran no kernel of the program's
    return 100.0 * sum(b for _s, b in ops) \
        / ctx.peak["hbm_bytes_per_s"] / seconds
