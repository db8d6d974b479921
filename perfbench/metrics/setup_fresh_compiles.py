"""Executables the constructor had XLA compile, not load from the
persistent cache (engine_stats `startup.compile.fresh_compiles`): 0 says
the run's start was warm. None where the program keeps no start-up
record."""


def read(ctx):
    startup = ctx.stats_ready.get("startup")
    if not startup:
        return None
    return float(int(startup["compile"]["fresh_compiles"]))
