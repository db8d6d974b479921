"""90th percentile of the samples first_token_host_lag_ms_mean averages."""
import phases
import waits


def read(ctx):
    return phases.from_events(ctx, waits.lag_ms_p90)
