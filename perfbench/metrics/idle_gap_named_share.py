"""Share of the device's idle time inside the capture that a `polykey/`
host span overlaps: how much of the idleness the engine's phases can
name (the rest is the engine thread between phases, or another thread)."""
import phases


def read(ctx):
    return phases.from_events(ctx, phases.idle_gap_named_share)
