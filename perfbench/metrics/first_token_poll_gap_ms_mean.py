"""The engine's own bound on first_token_host_lag_ms_mean, with no capture:
per prefill dispatch whose first tokens were read, the time since the
engine last looked and found it unfinished, or since its dispatch call
returned (engine_stats first_token_poll_gap_seconds / _count, close minus
open). The lag is never larger."""
import waits


def read(ctx):
    return waits.poll_gap_ms_mean(ctx)
