"""Share of the traced stretch, on the busiest device, in which a
collective runs and no compute operation does. A cell on one chip has no
collectives: nothing to read."""


def read(ctx):
    red = ctx.reduced
    if red is None:
        return None
    dev = red.busiest()
    if not dev.intervals("collective"):
        return None
    return 100.0 * dev.exposed("collective", ("conv", "other")) \
        / red.window_s
