"""The gated delta rule's decode state update (kernel
`gated_delta_state_update`, one call a linear-attention layer and decode
step): least time by its roofline — every lane's float32 state read once
and written once, from the configuration's costs module; memory-bound —
over its device time in the trace."""
import kernel_costs


def read(ctx):
    kernel = (ctx.trace or {}).get("kernels", {}).get(
        "gated_delta_state_update")
    if not kernel or not kernel.get("count"):
        return None
    costs = kernel_costs.for_spec(ctx.spec)
    if not hasattr(costs, "gated_delta_state_update"):
        return None
    lanes = ctx.spec["engine"]["max_decode_slots"]
    least, _ = kernel_costs.roofline_seconds(
        costs.gated_delta_state_update(ctx.spec, lanes), ctx.peaks)
    return 100.0 * least * kernel["count"] / kernel["total_s"]
