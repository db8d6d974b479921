"""1 - union of device operation intervals over the traced window."""


def read(ctx):
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
