"""Flash attention kernel in prefill: least time by its roofline (causal
attention of the prompts whose first token arrived inside the traced
window, FLOPs and bytes from shapes; compute-bound from ~250 tokens up)
over its device time in the trace."""
import kernel_costs


def read(ctx):
    kernel = ctx.trace["kernels"].get("flash_attention")
    start, stop = ctx.samples["meta"]["traced"]["start"], \
        ctx.samples["meta"]["traced"]["stop"]
    if not kernel or start is None or stop is None:
        return None
    layers = ctx.spec["num_hidden_layers"]
    least = 0.0
    for r in ctx.samples["requests"]:
        if r["times"] and start <= r["times"][0] <= stop:
            cost = kernel_costs.flash_prefill_call(ctx.spec, r["prompt_tokens"])
            least += layers * kernel_costs.roofline_seconds(cost, ctx.peaks)[0]
    return 100.0 * least / kernel["total_s"] if least else None
