"""Set-up after the engine's constructor returned: the narrowed head, the
gateway coming up, the served sample for the reference, the clients' ramp
(the run's `t_open` less engine_stats `startup.t_end`, one clock). None
where the program keeps no start-up record."""


def read(ctx):
    startup = ctx.stats_ready.get("startup")
    if not startup:
        return None
    return ctx.samples["meta"]["t_open"] - startup["t_end"]
