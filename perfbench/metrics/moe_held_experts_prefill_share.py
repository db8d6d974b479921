"""Share of the traced window the device spent in the held experts' product
(kernel `moe_held_experts`) INSIDE the prefill program: there the masked
one-pass form computes rows x experts held, `experts held / top-k` times
the routed arithmetic, compute-bound from ~128 rows — what a sorted
dropless grouped product at prefill widths would start from. The decode
program's calls are metrics/moe_held_experts_roofline.py's. Nothing where
the trace holds no such call."""

PROGRAM = "jit__prefill_fn/"


def read(ctx):
    trace = ctx.trace or {}
    seconds = sum(
        op["total_s"] for name, op in trace.get("ops", {}).items()
        if name.startswith(PROGRAM)
        and name[len(PROGRAM):].startswith("moe_held_experts"))
    if not seconds or not trace.get("window_s"):
        return None
    return 100.0 * seconds / trace["window_s"]
