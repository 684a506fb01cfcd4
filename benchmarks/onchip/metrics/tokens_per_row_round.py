"""Speculation layer: tokens committed per request per engine round
(``step_round``'s ``committed`` over the rows active in each round)."""
import math

from harness import stats


def read(ctx):
    v = stats.tokens_per_row_round(ctx.window)
    return None if math.isnan(v) else v
