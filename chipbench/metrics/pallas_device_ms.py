"""Device time a traced step in the program's own Pallas kernels: the
``custom-call``s the step-program map names ``dl4j_*`` (the kernel's
``name=``), on the busiest device."""

from chipbench import programspans as ps


def read(ctx):
    ops = ps.kernel_ops(ctx.reduced, ps.of(ctx).maps)
    if ops is None:
        return None
    return 1e3 * sum(s for s, _b in ops) / ctx.reduced.steps
