"""Median send -> first token over the requests sent inside the window:
watched beside the bounded mean and 90th percentile."""
import estimators


def read(ctx):
    return estimators.ttft_ms_p50(ctx.samples)
