"""``host:gc`` span time (the program's ``gc.callbacks`` hook) inside the
traced stretch, a step."""

from chipbench import programspans as ps


def read(ctx):
    j = ps.of(ctx)
    if not j.iterations:
        return None
    return ps.gc_pause_ms_per_step(ctx.reduced, j.spans, j.traced)
