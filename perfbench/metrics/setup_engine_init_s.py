"""Set-up inside the engine's constructor: parameters placed, pools made,
every served shape warmed (engine_stats `startup.t_end` less `t_begin`).
None where the program keeps no start-up record."""


def read(ctx):
    startup = ctx.stats_ready.get("startup")
    if not startup:
        return None
    return startup["t_end"] - startup["t_begin"]
