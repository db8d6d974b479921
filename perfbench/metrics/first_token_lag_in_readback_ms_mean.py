"""The part of first_token_host_lag_ms_mean during which the engine thread
sat in `polykey/readback_wait`, blocked on a decode block's tokens: what
waking for a landed first token would remove (the rest is host work)."""
import phases
import waits


def read(ctx):
    return phases.from_events(ctx, waits.lag_in_readback_ms_mean)
