"""Share of the decode program's device time spent in the latent layers'
decode read (kernel `mla_latent_decode`): the kernel's device seconds
inside `jit__decode_fn` over the seconds of that program's executions in
the capture. It says when attention, not the held experts' weights, sets
the step: it grows with the contexts, the weights' share does not. Nothing
where the trace holds no such kernel (another model, the parent)."""

PROGRAM = "jit__decode_fn"


def read(ctx):
    trace = ctx.trace or {}
    kernel = trace.get("kernels", {}).get("mla_latent_decode")
    program = trace.get("modules", {}).get(PROGRAM)
    if not kernel or not program or not program.get("total_s"):
        return None
    seconds = kernel.get("by_program", {}).get(PROGRAM)
    if not seconds:
        return None
    return 100.0 * seconds / program["total_s"]
