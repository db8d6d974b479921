"""Mean time from the prefill dispatch that completes the prompt to the
first token in the engine's hand (prefill_dispatched -> first_token: the
device queue behind decode blocks, the prefill, the readback and the
resolve), same requests as ttft_queue_ms_mean. With the other two phases
it partitions Usage.ttft_ms."""
import phases


def read(ctx):
    return phases.ttft_phase_ms_mean(ctx, "first_token")
