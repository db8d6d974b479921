"""Median wait of a prefill in the device's queue: start of the n-th
`polykey/prefill` host span to start of the n-th execution of the prefill
program on the device, joined in order inside the capture."""
import statistics

import phases


def waits_p50(extracted: dict):
    waits = phases.join_in_order(
        [start for start, _ in phases.spans(extracted, "prefill")],
        phases.program_starts(extracted, "jit__prefill_fn"))
    return statistics.median(waits) / 1e6 if waits else None


def read(ctx):
    return phases.from_events(ctx, waits_p50)
