"""Share of the prefill windows of a stateful model that started from the
end state of the row above in the same dispatch (a prompt covered by
several windows: engine_stats state_windows_chained /
prefill_windows_dispatched, close minus open). Nothing where the program
has no such counter."""
import phases


def read(ctx):
    chained = phases.delta(ctx, "state_windows_chained")
    windows = phases.delta(ctx, "prefill_windows_dispatched")
    if chained is None or not windows:
        return None
    return 100.0 * chained / windows
