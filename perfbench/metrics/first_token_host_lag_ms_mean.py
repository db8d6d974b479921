"""Mean time a finished first token waits for the host: end of the n-th
execution of the prefill program on the device to the start of the n-th
`polykey/first_token` host span, joined in order inside the capture
(waits.lag_intervals; None where the two do not pair off)."""
import phases
import waits


def read(ctx):
    return phases.from_events(ctx, waits.lag_ms_mean)
