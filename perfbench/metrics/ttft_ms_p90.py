"""90th percentile of send -> first token, same samples as ttft_ms_mean."""
import estimators


def read(ctx):
    return estimators.ttft_ms_p90(ctx.samples)
