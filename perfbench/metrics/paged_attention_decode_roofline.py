"""Paged decode attention kernel: least time by its roofline (K/V of the
live tokens, from shapes; memory-bound at 4 FLOP per byte) over its
device time in the trace."""
import counters
import kernel_costs


def read(ctx):
    kernel = ctx.trace["kernels"].get("paged_attention_decode")
    live = counters.live_tokens(ctx)
    if not kernel or live is None:
        return None
    lanes = ctx.spec["engine"]["max_decode_slots"]
    cost = kernel_costs.paged_decode_call(ctx.spec, live, lanes)
    least, _ = kernel_costs.roofline_seconds(cost, ctx.peaks)
    return 100.0 * least * kernel["count"] / kernel["total_s"]
