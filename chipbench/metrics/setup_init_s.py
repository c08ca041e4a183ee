"""Seconds inside the networks' ``init()`` before the window: the total
of the program's ``net:init`` spans (``chipbench.buildspans``). ``init()``
draws every parameter with a small jitted program of its own, some fifty
of them for a ResNet-50, each a read of JAX's persistent cache on a warm
machine; the harness's weights from the seed replace the values later and
are not in this span."""

from chipbench import buildspans as bs


def read(ctx):
    return bs.reading(ctx, "init_s")
