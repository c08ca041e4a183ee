"""Median over the traced steps of the busiest device's op time in fusions
the step-program map marks ``mixed``: one op doing two phases' work, which
nothing can split. In the CNN programs that is every weight-gradient
convolution the compiler fused with Adam's update (backward pass and
updater in one fusion), so this is where their updater's time is read; in
the language models it is the weight-gradient products fused so. It is
part of ``phase_unsure_share``."""

from chipbench import programspans as ps


def read(ctx):
    return ps.phase_ms(ctx.reduced, ps.of(ctx).maps, ps.MIXED)
