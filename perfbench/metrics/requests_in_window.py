"""Requests sent inside the window that answered: the sample size behind
the TTFT numbers."""
import estimators


def read(ctx):
    return estimators.requests_in_window(ctx.samples)
