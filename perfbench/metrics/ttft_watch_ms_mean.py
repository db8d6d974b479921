"""Mean send -> first token in a cell whose end-to-end metric is the
token rate: watched, not bounded."""
import estimators


def read(ctx):
    return estimators.ttft_ms_mean(ctx.samples)
