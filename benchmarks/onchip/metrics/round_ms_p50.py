"""Engine layer: median host wall time of one ``step_round`` (which ends
in the verdict-packet fetch, so it waits for the device)."""
from harness import stats


def read(ctx):
    return stats.round_ms_p50(ctx.window) if ctx.window.rounds else None
