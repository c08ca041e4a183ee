"""Median host time of one iteration of the fit loop over the traced
steps: from its ``fit:pull`` span's start to its ``fit:listeners(done)``
span's end (the program's tracer ring).

The traced steps are the 16 right after the profile's sync, where nothing
holds the host back: this is the host's own cost of an iteration. Over the
whole window the runtime's queue paces the host to the device's step (the
wait sits in ``fit:prepare``), which ``step_device_ms`` already says."""

from chipbench import programspans as ps


def read(ctx):
    its = ps.of(ctx).iterations
    return ps.host_step_ms(its) if its else None
