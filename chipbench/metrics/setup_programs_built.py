"""Programs handed to the backend before the window: the program's
``compile:backend`` spans there, whatever caused them
(``chipbench.buildspans``): the step, the small programs of ``init()``,
the harness's own leaf makers and change norms, the updater's state."""

from chipbench import buildspans as bs


def read(ctx):
    return bs.reading(ctx, "programs_built")
