"""Most KV pages in use at any poll of the window, as a share of the pool
(engine_stats kv_device_pages / (pages_total - 1), polled twice a second)."""


def read(ctx):
    polls = [p for p in ctx.polls + [ctx.stats_open, ctx.stats_close]
             if "kv_device_pages" in p]
    if not polls:
        return None
    pool = int(ctx.stats_open["pages_total"]) - 1
    return 100.0 * max(int(p["kv_device_pages"]) for p in polls) / pool
