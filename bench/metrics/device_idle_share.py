"""Share of the traced window in which no operation ran on the device,
mean over the cell's chips."""


def read(ctx):
    return 100.0 * (1.0 - ctx.trace.busy_s(ctx.red) / ctx.red.window_s)
