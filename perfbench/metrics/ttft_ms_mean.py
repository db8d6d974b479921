"""Mean send -> first token over every request sent inside the window
that answered (host clock)."""
import estimators


def read(ctx):
    return estimators.ttft_ms_mean(ctx.samples)
