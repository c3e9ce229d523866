"""The device's idle share of the traced window: 1 - busy / window, with
busy the union of the intervals in which an operation ran, averaged over
the cell's chips, in percent."""


def read(ctx):
    r = ctx.reduced
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
