"""Mean queue time behind an `_admit` visit that left the request waiting
for room: no free slot, or no pages (engine_stats
ttft_queue_seconds{no_slot} + {no_pages} / ttft_phase_count, close minus
open)."""
import waits


def read(ctx):
    return waits.queue_cause_ms_mean(ctx, "no_slot", "no_pages")
