"""Of the busiest device's idle time in the traced stretch, the share in
gaps that began before the host had finished dispatching the step the
device ran next: the device waited for the host. The rest is the device
idle with its work already queued.

Read over the 16 traced steps right after the profile's sync, while the
host's lead is still growing (``dispatch_lead_ms``): the first gap after
the sync is the host's by construction, and a host that is slow only once
its lead has settled would not show here."""

from chipbench import programspans as ps


def read(ctx):
    j = ps.of(ctx)
    if not j.iterations or j.offset is None:
        return None
    return ps.idle_host_bound_share(ctx.reduced, j.iterations, j.offset)
