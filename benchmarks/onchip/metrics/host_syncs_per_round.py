"""Engine layer: device -> host syncs per engine round: the program's
``engine.fetch`` spans (one per sync, the engine's ``host_fetches``
tally) that start in the traced window over the ``engine.round`` spans
that start in it.  None where the trace holds no ``engine.round``
span."""
ROUND, FETCH = "engine.round", "engine.fetch"


def read(ctx):
    r = ctx.reduced
    if r is None:
        return None
    lo, hi = r.window
    rounds = sum(1 for n, s, _ in r.host if n == ROUND and lo <= s < hi)
    if not rounds:
        return None
    fetches = sum(1 for n, s, _ in r.host if n == FETCH and lo <= s < hi)
    return fetches / rounds
