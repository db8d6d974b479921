"""The latent layers' decode read (kernel `mla_latent_decode`, one call a
latent-attention layer and decode step): least time by its roofline — the
ONE row of every live token read once for all heads, each lane's absorbed
query heads in and latent sums out, both products' operations, from the
configuration's costs module at the PUBLISHED row width; the larger of the
two times (157 FLOP a byte at the cell's contexts, 242 in the limit against
the chip's 240.5: memory-bound, on the ridge) — over its device time in the
trace. Nothing where the trace holds no such kernel (another model, the
parent) or the costs module reckons none."""
import counters
import kernel_costs


def read(ctx):
    kernel = (ctx.trace or {}).get("kernels", {}).get("mla_latent_decode")
    if not kernel or not kernel.get("count") or not kernel.get("total_s"):
        return None
    costs = kernel_costs.for_spec(ctx.spec)
    live = counters.live_tokens(ctx)
    if live is None or not hasattr(costs, "mla_latent_decode"):
        return None
    lanes = ctx.spec["engine"]["max_decode_slots"]
    least, _ = kernel_costs.roofline_seconds(
        costs.mla_latent_decode(ctx.spec, lanes, live_tokens=live), ctx.peaks)
    return 100.0 * least * kernel["count"] / kernel["total_s"]
