"""Engine layer: median host time of one engine round that the chip waits
on, in ms.  For each ``engine.round`` span of the program inside the
traced window: the stretches of the round in which chip 0 ran no
operation, less those that lie under one of the program's
``engine.fetch`` spans (each overlap counted once).  What is left is the
host's own work with the chip idle: a call that blocks on the device,
fetch or dispatch, holds the chip busy and is not in it, nor host work
that device work hides.  It is the per-round form of
``device_idle_host_share``.  None where the trace holds no
``engine.round`` span (a program without the spans)."""
import bisect
import statistics

from harness.trace import gaps, union

ROUND, FETCH = "engine.round", "engine.fetch"


def read(ctx):
    r = ctx.reduced
    if r is None or not r.chips:
        return None
    lo, hi = r.window
    rounds = [(s, e) for n, s, e in r.host
              if n == ROUND and lo <= s and e <= hi]
    if not rounds:
        return None
    idle = gaps([(o.start, o.end) for o in r.ops if o.chip == 0], lo, hi)
    idle_starts = [a for a, _ in idle]
    fetches = []                         # the fetch spans' union, merged
    for f0, f1 in sorted((s, e) for n, s, e in r.host if n == FETCH):
        if fetches and f0 <= fetches[-1][1]:
            fetches[-1] = (fetches[-1][0], max(fetches[-1][1], f1))
        else:
            fetches.append((f0, f1))
    fetch_starts = [s for s, _ in fetches]
    own = []
    for s, e in rounds:
        t = 0.0
        # idle gaps are disjoint and sorted: the one started last before
        # the round may reach into it
        for a, b in idle[max(0, bisect.bisect_left(idle_starts, s) - 1):
                         bisect.bisect_left(idle_starts, e)]:
            a, b = max(a, s), min(b, e)
            if b <= a:
                continue
            # merged fetches are disjoint and sorted too
            near = fetches[max(0, bisect.bisect_left(fetch_starts, a) - 1):
                           bisect.bisect_right(fetch_starts, b)]
            t += (b - a) - union(near, a, b)
        own.append(1e-6 * t)
    return statistics.median(own)
