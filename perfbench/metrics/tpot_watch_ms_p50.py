"""Median over streams of the same per-stream time per output token as
tpot_ms_mean: watched, not bounded (it jumps between block-quantized
modes where output lengths are mixed)."""
import estimators


def read(ctx):
    return estimators.tpot_ms_p50(ctx.samples)
