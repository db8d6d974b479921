"""Device time of the prefill program in the traced window per thousand
prompt tokens the engine dispatched in it (engine_stats
prefill_tokens_total at trace start and stop)."""
import counters


def read(ctx):
    prefill = ctx.trace["modules"].get("jit__prefill_fn")
    tokens = counters.traced_delta(ctx, "prefill_tokens_total")
    if not prefill or not tokens:
        return None
    return 1000.0 * prefill["total_s"] / (tokens / 1000.0)
