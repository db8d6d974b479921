"""Mean queue time with no decision behind it: from a request's arrival to
the first `_admit` visit that saw it, plus its wait inside the visit that
admitted it: the engine thread was in another phase (engine_stats
ttft_queue_seconds{loop} / ttft_phase_count, close minus open). With
ttft_queue_budget_ms_mean and ttft_queue_capacity_ms_mean it adds up to
ttft_queue_ms_mean."""
import waits


def read(ctx):
    return waits.queue_cause_ms_mean(ctx, "loop")
