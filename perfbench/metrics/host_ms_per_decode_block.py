"""Engine-thread milliseconds of host work per dispatched decode block:
the seconds of every loop phase that does work (admit, restore, chunk,
dispatch, resolve, process) less the blocking readback inside process
(readback_wait), over the blocks dispatched (engine_stats phase_seconds
and blocks_dispatched, close minus open). idle_wait is not work."""
import phases


def read(ctx):
    blocks = phases.delta(ctx, "blocks_dispatched")
    waited = phases.delta(ctx, "phase_seconds", "readback_wait")
    worked = [phases.delta(ctx, "phase_seconds", name)
              for name in phases.LOOP_WORK_PHASES]
    if not blocks or waited is None or None in worked:
        return None
    return 1000.0 * (sum(worked) - waited) / blocks
