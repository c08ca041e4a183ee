"""Images whose update step completed in the window (steps x batch), over
the whole window's seconds (``fit()`` call to ``block_until_ready`` on the
parameters), over the cell's chips."""


def read(ctx):
    r = ctx.result
    return r["steps"] * r["batch"] / r["window_s"] / r["chips"]
