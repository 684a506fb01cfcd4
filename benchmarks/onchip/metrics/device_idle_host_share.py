"""Device layer: the share of the traced window in which chip 0 sat idle
while the host ran the engine's own work, in percent.  Each idle gap is
put down to the innermost ``engine.*`` span of the program covering its
midpoint (JAX's own events beneath it count toward it); a gap under
``engine.fetch`` (the host waiting on a transfer) or under no engine
span is left out.  None where the trace holds no ``engine.round``
span."""
import bisect

from harness.trace import gaps

PREFIX, ROUND, FETCH = "engine.", "engine.round", "engine.fetch"


def read(ctx):
    r = ctx.reduced
    if r is None or not r.chips:
        return None
    # outer before inner at a shared start: walking back from a point
    # meets the innermost covering span first (one thread's spans nest)
    spans = sorted(((s, -e, n) for n, s, e in r.host
                    if n.startswith(PREFIX)))
    if not any(n == ROUND for _, _, n in spans):
        return None
    starts = [s for s, _, _ in spans]
    reach, far = [], float("-inf")       # latest end among spans[:i + 1]
    for _, neg_end, _ in spans:
        far = max(far, -neg_end)
        reach.append(far)
    lo, hi = r.window
    host_s = 0.0
    for a, b in gaps([(o.start, o.end) for o in r.ops if o.chip == 0],
                     lo, hi):
        t = (a + b) / 2
        i = bisect.bisect_right(starts, t) - 1
        while i >= 0 and reach[i] >= t and -spans[i][1] < t:
            i -= 1
        if i >= 0 and reach[i] >= t and spans[i][2] != FETCH:
            host_s += b - a
    return 100.0 * host_s / (hi - lo)
