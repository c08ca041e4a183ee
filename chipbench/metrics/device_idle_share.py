"""1 - union of device-operation intervals over the traced stretch, on the
busiest device."""


def read(ctx):
    red = ctx.reduced
    if red is None:
        return None
    return 100.0 * (1.0 - red.busiest().busy() / red.window_s)
