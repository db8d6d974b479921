"""Mean queue time behind an `_admit` visit that left the request waiting
because the iteration's prefill budget was spent, up to the next visit
(engine_stats ttft_queue_seconds{budget} / ttft_phase_count, close minus
open)."""
import waits


def read(ctx):
    return waits.queue_cause_ms_mean(ctx, "budget")
