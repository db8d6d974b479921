"""Mean time between admission and the prefill dispatch that completes
the prompt (prefill_start -> prefill_dispatched: tokenized, pages
allocated, held on the host), same requests as ttft_queue_ms_mean."""
import phases


def read(ctx):
    return phases.ttft_phase_ms_mean(ctx, "prefill_wait")
