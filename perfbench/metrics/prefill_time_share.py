"""Share of the traced window the device spent in the prefill program."""


def read(ctx):
    prefill = ctx.trace["modules"].get("jit__prefill_fn")
    if not prefill:
        return None
    return 100.0 * prefill["total_s"] / ctx.trace["window_s"]
