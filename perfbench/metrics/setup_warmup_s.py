"""The constructor's warm-up stage: every served shape dispatched once,
each traced, lowered and loaded or built (engine_stats
`startup.stages.warmup`, a part of setup_engine_init_s). None where the
program keeps no start-up record or warmed nothing."""


def read(ctx):
    startup = ctx.stats_ready.get("startup")
    if not startup:
        return None
    return startup["stages"].get("warmup")
