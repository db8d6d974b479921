"""Share of the prefill rows the device computed that carried no prompt
token: bucket and group padding of bucketed prompts, the tail of a last
chunk (engine_stats 1 - prefill_rows_useful / prefill_rows_dispatched,
close minus open)."""
import phases


def read(ctx):
    useful = phases.delta(ctx, "prefill_rows_useful")
    dispatched = phases.delta(ctx, "prefill_rows_dispatched")
    if useful is None or not dispatched:
        return None
    return 100.0 * (1.0 - useful / dispatched)
