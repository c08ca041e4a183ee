"""Median over the traced steps of the busiest device's op time whose
step-program map entry says backward pass (``transpose(jvp(…))``), the
forward work a backward fusion recomputes included: it runs then. So is
the forward work a rematerialised stretch runs again: the map gives it
the backward phase with the ``remat`` mark, and ``remat_device_ms`` reads
that part of this time apart."""

from chipbench import programspans as ps


def read(ctx):
    return ps.phase_ms(ctx.reduced, ps.of(ctx).maps, "backward")
