"""Set-up before the engine's constructor began (the harness's clock to the
engine's, both CLOCK_MONOTONIC): the child's spawn, its imports, the
configuration's seeded weights. engine_stats `startup.t_begin` less the
run's `t_start`; with setup_engine_init_s and setup_after_engine_s it adds
up to setup_s. None where the program keeps no start-up record."""


def read(ctx):
    startup = ctx.stats_ready.get("startup")
    if not startup:
        return None
    return startup["t_begin"] - ctx.samples["meta"]["t_start"]
