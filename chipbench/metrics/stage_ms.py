"""Median ``fit:stage`` span over the traced steps: the host's time in
all ``stage_batch`` calls of one batch (the program's tracer ring)."""

from chipbench import programspans as ps


def read(ctx):
    its = ps.of(ctx).iterations
    return ps.stage_ms(its) if its else None
