"""Kernel layer: the paged-attention kernel's share of its roofline.

The least time the chip's peaks allow for the calls the window made —
per decoder forward, the larger of its FLOPs over peak FLOP/s and its
bytes over peak bandwidth (``flops.paged_attention_cost``: real queries,
each row's keys and values read once) — over the kernel's summed device
time in the trace, in percent."""
from harness import flops

KERNEL = r"^paged_decode_attention"


def read(ctx):
    if ctx.reduced is None or ctx.calls is None or ctx.peak is None:
        return None
    t = ctx.reduced.kernel_s(KERNEL)
    if t <= 0:
        return None
    least = 0.0
    for side, T, q_start, lens in ctx.calls.calls:
        f, b = flops.paged_attention_cost(ctx.dims[side], q_start, lens, T)
        least += flops.least_time(f, b, ctx.peak)[0]
    return 100.0 * least / t if least > 0 else None
