"""Median over the traced steps of the busiest device's op time whose
step-program map entry says updater (the ``dl4j_updater`` scope: gradient
normalisation, loss-scale un-scaling, Adam). The weight-gradient
convolutions the compiler fused with Adam's update are not here: they are
``mixed_device_ms``. On one chip every update is fused so (0.01-0.02 ms is
left, PR 26), and only the four-chip cell lists this metric: there the
gradients' exchange stands between the two and 1.4 ms is the updater's."""

from chipbench import programspans as ps


def read(ctx):
    return ps.phase_ms(ctx.reduced, ps.of(ctx).maps, "updater")
