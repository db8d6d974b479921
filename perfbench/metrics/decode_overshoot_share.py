"""Share of the decode lane-steps of lanes live at dispatch that
delivered no token: steps past a stream's end inside a block, whole
lookahead blocks of streams that had ended, cancelled lanes. Counted by
the engine when it processes a block (engine_stats
decode_lane_steps_overshoot / (delivered + overshoot), close minus
open)."""
import phases


def read(ctx):
    delivered = phases.delta(ctx, "decode_lane_steps_delivered")
    overshoot = phases.delta(ctx, "decode_lane_steps_overshoot")
    if delivered is None or overshoot is None:
        return None
    return phases.share(overshoot, delivered + overshoot)
