"""Of the held experts of the decode program's expert layers, the share
that at least one live lane chose, over the capture (engine_stats
held_experts_hit / (held_expert_calls x the experts a layer holds), read
beside the profiler's start and stop: counters.held_hit_share). It says
how much of the held experts' read was asked for — what
moe_held_experts_roofline and decode_mbu count as needed — and follows the
traffic and the router, not the program: `better` is nominal. Nothing
where the program has no such counters (a dense model, the parent)."""
import counters
import kernel_costs


def read(ctx):
    share = counters.held_hit_share(ctx, kernel_costs.for_spec(ctx.spec))
    return 100.0 * share["hit_share"] if share else None
