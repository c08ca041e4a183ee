"""Seconds of set-up spent tracing the step in Python: the
``compile:trace`` spans whose cause is a ``fit:build`` before the window
(``chipbench.buildspans``), the union of their intervals less what the
step's ``compile:lower`` and ``compile:backend`` cover, so a function
jitted inside the step counts once. JAX's persistent cache is keyed by the
lowered program, so a warm run pays this again in full."""

from chipbench import buildspans as bs


def read(ctx):
    return bs.reading(ctx, "step_trace_s")
