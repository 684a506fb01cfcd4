"""Output tokens committed in the window over the window's seconds."""
from harness import stats


def read(ctx):
    return stats.output_tokens_per_s(ctx.window)
