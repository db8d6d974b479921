"""Median of client TTFT minus the engine's own Usage.ttft_ms: what the
socket, the gateway and the streaming add before the first token."""
import statistics

import estimators


def read(ctx):
    values = estimators.gateway_overheads_ms(ctx.samples)
    return statistics.median(values) if values else None
