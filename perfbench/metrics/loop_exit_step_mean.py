"""The pass of a looped stack whose output the head read, averaged over
the live decode lane-steps of the capture, counted from 1: engine_stats
`loop_exits_by_step` (a vector, one entry a pass: the live lane-steps whose
exit rule chose that pass, counted on the device), read beside the
profiler's start and stop; sum (u + 1) n_u / sum n_u. At the published
threshold of 1 it reads the number of passes: every live lane's token went
through all of them and the head read the last. A program that lets a
position leave early shows here before it shows anywhere. Nothing where
the server has no such counter (a stack of one pass, the parent)."""


def read(ctx):
    traced = ctx.samples["meta"]["traced"]
    first, last = traced.get("stats_start"), traced.get("stats_stop")
    key = "loop_exits_by_step"
    if not first or not last or key not in last:
        return None
    before = first.get(key) or [0] * len(last[key])
    counts = [float(b) - float(a) for a, b in zip(before, last[key])]
    if not sum(counts):
        return None
    return sum((u + 1) * n for u, n in enumerate(counts)) / sum(counts)
