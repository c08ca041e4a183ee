"""Of the programs built before the window (``setup_programs_built``),
those JAX's persistent cache did not hold, so the backend compiled them
(``cache == "miss"`` on the ``compile:backend`` span). 0 is the only
reading at which THIS run's ``setup_s`` is a warm one; a first run on a
machine reads every program here. Like every per-layer metric it is read
in the traced run alone: the untraced runs whose ``setup_s`` is compared
carry no such reading and are judged by ``phases.first_steps`` still
(the spans are there in every run; the result line of an untraced run is
``chipbench/run.py``'s to widen)."""

from chipbench import buildspans as bs


def read(ctx):
    return bs.reading(ctx, "cache_misses")
