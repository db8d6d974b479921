"""Share of the traced window in collectives with no other operation
running on that chip."""


def read(ctx):
    return 100.0 * ctx.trace["collective_exposed_s"] / ctx.trace["window_s"]
