"""The Mamba-2 decode state update (kernel `ssm_state_update`, one call a
mixer layer and decode step): least time by its roofline — every lane's
float32 state read once and written once, from the configuration's costs
module; memory-bound — over its device time in the trace."""
import kernel_costs


def read(ctx):
    kernel = (ctx.trace or {}).get("kernels", {}).get("ssm_state_update")
    if not kernel or not kernel.get("count"):
        return None
    lanes = ctx.spec["engine"]["max_decode_slots"]
    cost = kernel_costs.for_spec(ctx.spec).ssm_state_update(ctx.spec, lanes)
    least, _ = kernel_costs.roofline_seconds(cost, ctx.peaks)
    return 100.0 * least * kernel["count"] / kernel["total_s"]
