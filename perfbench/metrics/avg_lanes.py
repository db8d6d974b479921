"""Step-weighted mean of live decode lanes over the window
(engine_stats lane_steps / steps_dispatched, close minus open)."""
import counters


def read(ctx):
    steps = counters.delta(ctx, "steps_dispatched")
    return counters.delta(ctx, "lane_steps") / steps if steps else None
