"""Mean time a request spent in the engine's submit queue before
admission took it (enqueued -> prefill_start: no slot, no pages, or the
prefill budget of that iteration spent), over the requests whose first
token resolved inside the window (engine_stats ttft_phase_seconds /
ttft_phase_count, close minus open)."""
import phases


def read(ctx):
    return phases.ttft_phase_ms_mean(ctx, "queue")
