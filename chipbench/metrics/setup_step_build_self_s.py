"""Seconds of set-up inside the dispatches that built the step and
outside JAX's three reported stages: the ``fit:build`` spans before the
window less what their ``compile:*`` children cover
(``chipbench.buildspans``): hashing the module for the cache's key, pjit's
own Python around the stages, and the first enqueue of the step. With
``setup_step_trace_s``, ``setup_step_lower_s`` and ``setup_step_backend_s``
it adds up to the ``fit:build`` total."""

from chipbench import buildspans as bs


def read(ctx):
    return bs.reading(ctx, "step_build_self_s")
