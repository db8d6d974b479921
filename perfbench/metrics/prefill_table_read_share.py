"""Of the positions the page tables of the prefill dispatches' rows span
(max_seq_len a row), the share whose keys a layer's attention gathered
from the pool and streamed through the blockwise kernel, over the capture
(engine_stats prefill_keys_read_total / prefill_keys_table_total, read
beside the profiler's start and stop). The program gathers the leading
pages that hold the dispatch's furthest position, a window's worth a step;
the engine counts by the same function. Nothing where the program has no such
counters (the parent: it reads every table whole, 100 % by construction)
or no prefill was dispatched between the readings."""
import phases


def read(ctx):
    traced = ctx.samples["meta"]["traced"]
    first, last = traced.get("stats_start"), traced.get("stats_stop")
    keys = ("prefill_keys_read_total", "prefill_keys_table_total")
    if not first or not last or any(
            key not in stats for key in keys for stats in (first, last)):
        return None
    got, table = (float(last[key]) - float(first[key]) for key in keys)
    return phases.share(got, table)
