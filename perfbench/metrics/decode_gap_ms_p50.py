"""Median device gap between consecutive executions of the decode block
program (trace, 'XLA Modules' line)."""
import statistics


def read(ctx):
    decode = ctx.trace["modules"].get("jit__decode_fn")
    if not decode or not decode["gaps_s"]:
        return None
    return 1000.0 * statistics.median(decode["gaps_s"])
