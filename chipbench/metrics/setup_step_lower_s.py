"""Seconds of set-up spent turning the step's jaxpr into an MLIR module:
the ``compile:lower`` spans whose cause is a ``fit:build`` before the
window (``chipbench.buildspans``), less what ``compile:backend`` covers.
Paid again on a warm run, like the trace: the cache's key is computed from
the module."""

from chipbench import buildspans as bs


def read(ctx):
    return bs.reading(ctx, "step_lower_s")
