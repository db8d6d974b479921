"""The held experts' product (kernel `moe_held_experts`) in the DECODE
program, one call an expert layer and step at lanes rows: least time by its
roofline — every held expert's weights read once, from the configuration's
costs module; memory-bound at decode widths — over its device time in the
trace. Read from the decode program's operations only: the prefill
program calls the same kernel at other widths."""
import kernel_costs

PROGRAM = "jit__decode_fn/"


def read(ctx):
    ops = (ctx.trace or {}).get("ops", {})
    mine = [op for name, op in ops.items()
            if name.startswith(PROGRAM)
            and name[len(PROGRAM):].startswith("moe_held_experts")]
    calls = sum(op["count"] for op in mine)
    seconds = sum(op["total_s"] for op in mine)
    if not calls or not seconds:
        return None
    rows = ctx.spec["engine"]["max_decode_slots"]
    cost = kernel_costs.for_spec(ctx.spec).moe_held_experts(ctx.spec, rows)
    least, _ = kernel_costs.roofline_seconds(cost, ctx.peaks)
    return 100.0 * least * calls / seconds
