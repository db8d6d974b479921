"""Device time of one decode block (median execution of the block
program in the trace) over its steps."""
import counters


def read(ctx):
    return counters.decode_step_device_ms(ctx)
