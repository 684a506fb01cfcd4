"""Process start until the window opens: weights, engine, compile or
cache load, warm-up."""


def read(ctx):
    return ctx.setup_s
