"""Collective operations the busiest device ran, over the traced steps."""


def read(ctx):
    red = ctx.reduced
    if red is None or not red.steps:
        return None
    n = len(red.busiest().intervals("collective"))
    return n / red.steps if n else None
