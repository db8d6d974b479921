"""Share of the traced window spent in collective operations."""


def read(ctx):
    return 100.0 * ctx.trace["collective_s"] / ctx.trace["window_s"]
