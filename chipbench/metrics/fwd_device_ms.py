"""Median over the traced steps of the busiest device's op time whose
step-program map entry (``deeplearning4j_tpu.profiler.stepprogram``) says
forward pass: the layers' ``jvp(dl4j_L<i>_…)`` work, the loss and the
on-device augmentation; forward work a rematerialised stretch runs again
in the backward pass counts in ``bwd_device_ms``. ``fwd + bwd + updater +
mixed`` plus the ops the map does not know is ``step_device_ms``; the own
time of a ``conditional`` or ``while`` op comes on top, since the trace
lists it beside its body's ops (a step of the sparse decoder whose routed
path takes a second pass). ``phase_unsure_share`` says how far to trust
the split."""

from chipbench import programspans as ps


def read(ctx):
    return ps.phase_ms(ctx.reduced, ps.of(ctx).maps, "forward")
