"""Process start -> window open (host clock): engine construction, seeded
weights, loading or compiling the cell's executables, the reference sample
request and the clients' ramp."""


def read(ctx):
    return ctx.setup_s
