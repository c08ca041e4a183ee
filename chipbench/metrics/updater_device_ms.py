"""Median over the traced steps of the busiest device's op time whose
step-program map entry says updater (the ``dl4j_updater`` scope: gradient
normalisation, loss-scale un-scaling, Adam). The weight-gradient
products the compiler fused with Adam's update are not here: they are
``mixed_device_ms``. In the one-chip CNN cells every update is fused so
(0.01-0.02 ms is left), and they do not list this metric. It reads the
four-chip cell, where the gradients' exchange stands between the two, and
the sparse and hybrid decoders, where many updates are loop fusions of
their own: the leaves no product's fusion takes, and in the hybrid
decoder the thirteen large weights whose update the program keeps apart
from their gradient's product at 32,768 rows."""

from chipbench import programspans as ps


def read(ctx):
    return ps.phase_ms(ctx.reduced, ps.of(ctx).maps, "updater")
