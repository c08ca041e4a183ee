"""Seconds of set-up inside the backend for the step: the
``compile:backend`` spans whose cause is a ``fit:build`` before the window
(``chipbench.buildspans``). On a hit of JAX's persistent cache this is the
cache's read, the executable's deserialisation and its load onto the
chip; on a miss it is XLA's compile. ``setup_cache_misses`` says which."""

from chipbench import buildspans as bs


def read(ctx):
    return bs.reading(ctx, "step_backend_s")
