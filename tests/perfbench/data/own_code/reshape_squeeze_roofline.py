"""The share of its roofline of the operation the test configuration
`own-code` names as a kernel of its own (`reshape_squeeze`, the pool
relayout of the decode step): least time from the configuration's costs
module over its device time in the trace."""
import kernel_costs


def read(ctx):
    kernel = (ctx.trace or {}).get("kernels", {}).get("reshape_squeeze")
    if not kernel:
        return None
    cost = kernel_costs.for_spec(ctx.spec).reshape_squeeze(ctx.spec)
    least, _ = kernel_costs.roofline_seconds(cost, ctx.peaks)
    return 100.0 * least * kernel["count"] / kernel["total_s"]
