"""The guard on ``fwd_device_ms``, ``bwd_device_ms`` and
``updater_device_ms``: device-op time of the traced stretch whose phase the
step-program map cannot say (no entry, ``other``: compiler-made layout
copies; or ``mixed``: one fusion doing two phases' work) over all device-op
time, on the busiest device."""

from chipbench import programspans as ps


def read(ctx):
    return ps.unsure_share(ctx.reduced, ps.of(ctx).maps)
