"""How far ahead of the device the host runs: median over the traced
steps of the device start of step k's program minus the end of the
program's ``fit:dispatch`` span k, on one clock (``programspans.
clock_offset``). Negative: the device waited for the host.

The traced steps are the 16 right after the profile's start, which syncs
host and device: the lead starts at 0 there and grows by the step's time
less the host's own (``host_step_ms``) each step until the runtime's queue
holds the host back. The median reads that ramp, not the window's settled
lead (which is longer)."""

from chipbench import programspans as ps


def read(ctx):
    j = ps.of(ctx)
    if not j.iterations or j.offset is None:
        return None
    return ps.dispatch_lead_ms(ctx.reduced, j.iterations, j.offset)
