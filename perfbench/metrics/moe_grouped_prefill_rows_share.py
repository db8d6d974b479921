"""Share of the prefill rows the device computed whose expert layers ran
the held experts' product over rows sorted by expert (the grouped form:
each chosen pair once) and not every row against every held expert — by
the program's rule, dispatches of more than 256 rows (engine_stats
prefill_rows_grouped_experts / prefill_rows_dispatched, close minus
open). A share of two counters read at the same moments, so a profiler
stop that stalls admission does not bend it. Nothing where the program
has no such counter."""
import phases


def read(ctx):
    return phases.share(
        phases.delta(ctx, "prefill_rows_grouped_experts"),
        phases.delta(ctx, "prefill_rows_dispatched"))
