"""Model-step layer: target forward FLOPs of every committed token
(2 x matmul weights + attention over the token's context, from the cut
target's shapes; every committed token is a position the target
verified) over the window's seconds times the chip's peak bf16 FLOP/s,
in percent."""
from harness import flops


def read(ctx):
    if ctx.peak is None:
        return None
    w, m = ctx.window, ctx.dims["target"]
    ctxs = []
    for r in w.reqs.values():
        k = 0
        for t, n in r.deliveries:
            if w.t0 <= t <= w.t_end:
                ctxs.extend(range(r.prompt_len + k, r.prompt_len + k + n))
            k += n
    if not ctxs:
        return None
    return 100.0 * flops.forward_flops(m, ctxs) / (
        w.seconds * ctx.peak["bf16_flops_per_s"])
