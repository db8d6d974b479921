"""Share of the decode program's device time spent sorting the logits for
the sampled head: the device seconds of the `sort` operations over
`[lanes, vocabulary]` inside `jit__decode_fn` over the seconds of that
program's executions in the capture. The engine's exact sampler sorts the
whole vocabulary once a step for any batch with a sampled lane, truncated
or not (engine/sampling.py `_trunc_thresholds`); a greedy batch has no such
operation. It says how much of a sampled cell's step is the sampler and
not the model: `decode_mbu` falls by it and no kernel's roofline shows it.
Nothing where the trace holds no such operation (greedy requests)."""

import re

PROGRAM = "jit__decode_fn"
SHAPE = re.compile(r"\[(\d+),(\d+)\]$")


def read(ctx):
    trace = ctx.trace or {}
    program = trace.get("modules", {}).get(PROGRAM)
    if not program or not program.get("total_s"):
        return None
    vocabulary = str(ctx.spec["vocab_size"])
    seconds = 0.0
    for name, op in trace.get("ops", {}).items():
        shape = SHAPE.search(name)
        if (name.startswith(PROGRAM + "/sort") and shape
                and shape.group(2) == vocabulary):
            seconds += op["total_s"]
    if not seconds:
        return None
    return 100.0 * seconds / program["total_s"]
