"""Executables built between window open and close (engine_stats
compiles.executables): anything above 0 compiled inside the window."""


def read(ctx):
    return float(int(ctx.stats_close["compiles"]["executables"])
                 - int(ctx.stats_open["compiles"]["executables"]))
