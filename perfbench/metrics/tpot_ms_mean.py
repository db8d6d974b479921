"""Mean time per output token over all tokens of the window (host clock):
sum over streams of (last - first token time) over the sum of
(tokens - 1), streams with at least 32 tokens inside. Token-weighted and
not a median over streams: a stream's own value is quantized by the decode
block (a 32-token and a 40-token tool turn sit 4 ms apart), so the median
of a mixture jumps between modes from run to run, and a plain mean over
streams is moved by the streams the window's edge cut short (PERF.md,
noise study)."""
import estimators


def read(ctx):
    return estimators.tpot_ms_mean(ctx.samples)
