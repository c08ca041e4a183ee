"""Median over the traced steps of the device-busy time inside one step
program's run on the busiest device, in ms (a statistic of pieces: a
per-layer metric only)."""

import statistics

from chipbench.trace import union_seconds


def read(ctx):
    red = ctx.reduced
    if red is None or not red.steps:
        return None
    dev = red.busiest()
    per_step = []
    for lo, hi, _name in dev.modules:
        per_step.append(union_seconds(
            [(s, e) for s, e in dev.intervals() if s >= lo and e <= hi]))
    return 1e3 * statistics.median(per_step)
