"""Of the least bytes of a decode step, the share that is the same layer
matrices read AGAIN by a looped stack's later passes, over the capture:
engine_stats `loop_layer_passes` (layer applications of the decode program:
passes x layers a dispatched step) over `steps_dispatched`, less one
application of each layer, times a layer's matrix bytes
(costs `reread_bytes`), over `decode_step_bytes` at the capture's live
tokens. It is what a stack that kept a layer on the chip between its
passes would not read; it follows the model and the contexts, not the
program: `better` is nominal. Nothing where the server has no such counter
(a stack of one pass, the parent) or the costs count no re-read."""
import counters
import kernel_costs


def read(ctx):
    traced = ctx.samples["meta"]["traced"]
    first, last = traced.get("stats_start"), traced.get("stats_stop")
    costs = kernel_costs.for_spec(ctx.spec)
    if (not first or not last or "loop_layer_passes" not in last
            or not hasattr(costs, "reread_bytes")):
        return None
    passes = float(last["loop_layer_passes"]) - float(
        first.get("loop_layer_passes", 0))
    steps = float(last["steps_dispatched"]) - float(first["steps_dispatched"])
    live = counters.live_tokens(ctx)
    if not steps or live is None:
        return None
    return (100.0 * costs.reread_bytes(ctx.spec, passes / steps)
            / costs.decode_step_bytes(ctx.spec, live))
