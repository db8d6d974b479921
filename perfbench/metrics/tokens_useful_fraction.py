"""Share of dispatched decode lane-steps whose token reached a request
(engine_stats tokens_useful / tokens_dispatched, close minus open)."""
import counters


def read(ctx):
    dispatched = counters.delta(ctx, "tokens_dispatched")
    if not dispatched:
        return None
    return 100.0 * counters.delta(ctx, "tokens_useful") / dispatched
