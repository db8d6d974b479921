"""Weight and K/V bytes one decode step must read (from shapes) over the
measured device time of a step, as a share of the chip's HBM bandwidth."""
import counters
import kernel_costs


def read(ctx):
    step_ms = counters.decode_step_device_ms(ctx)
    live = counters.live_tokens(ctx)
    if not step_ms or live is None:
        return None
    needed = kernel_costs.for_spec(ctx.spec).decode_step_bytes(ctx.spec, live)
    return 100.0 * needed / (step_ms / 1000.0) / ctx.peaks["hbm_bytes_per_s"]
