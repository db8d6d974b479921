"""Seconds JAX spent tracing and lowering inside the constructor
(engine_stats `startup.compile` `trace_s` + `lower_s`): what no compile
cache saves, since the cache's key is computed from the lowered module.
None where the program keeps no start-up record."""


def read(ctx):
    startup = ctx.stats_ready.get("startup")
    if not startup:
        return None
    return startup["compile"]["trace_s"] + startup["compile"]["lower_s"]
