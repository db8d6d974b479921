"""Share of the decode program's device time spent in the linear-attention
layers' state update (kernel `gated_delta_state_update`, one call a layer
and decode step): the kernel's device seconds inside `jit__decode_fn` over
the seconds of that program's executions in the capture. It says what the
kernel's roofline cannot: how much of the step the state update IS — it
does not grow with the contexts, the attending layers' share does. Nothing
where the trace holds no such kernel (another model, the parent of the PR
that brought a configuration)."""

PROGRAM = "jit__decode_fn"


def read(ctx):
    trace = ctx.trace or {}
    kernel = trace.get("kernels", {}).get("gated_delta_state_update")
    program = trace.get("modules", {}).get(PROGRAM)
    if not kernel or not program or not program.get("total_s"):
        return None
    seconds = kernel.get("by_program", {}).get(PROGRAM)
    if not seconds:
        return None
    return 100.0 * seconds / program["total_s"]
