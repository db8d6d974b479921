"""Streamed tokens per second over all clients, edge-aligned (host clock):
the tokens after the first arrival instant inside the window, over the
time from that instant to the last one inside it (estimators.py)."""
import estimators


def read(ctx):
    return estimators.output_tok_s(ctx.samples)
