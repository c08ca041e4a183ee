"""Median over the traced steps of the busiest device's op time whose
step-program map entry (``deeplearning4j_tpu.profiler.stepprogram``) says
forward pass: the layers' ``jvp(dl4j_L<i>_…)`` work, the loss and the
on-device augmentation. ``fwd + bwd + updater + mixed`` plus the ops the
map does not know is ``step_device_ms``; ``phase_unsure_share`` says how
far to trust the split."""

from chipbench import programspans as ps


def read(ctx):
    return ps.phase_ms(ctx.reduced, ps.of(ctx).maps, "forward")
