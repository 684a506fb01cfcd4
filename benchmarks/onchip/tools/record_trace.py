"""Record a small profiler trace on the chip: one paged-attention call,
one fused verify call and one matmul, each under a host annotation.

    python3 benchmarks/onchip/tools/record_trace.py OUT_DIR

Writes the ``.xplane.pb`` under OUT_DIR and prints, per plane and line,
the event count and the most frequent event names with their stat keys.
The benchmark's trace reduction is tested against such a trace.
"""
from __future__ import annotations

import collections
import glob
import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "..",
                                "src"))
from repro.kernels import ops  # noqa: E402


def workload():
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    B, T, H, KV, hd, ps, P = 4, 2, 8, 2, 128, 16, 65
    q = jax.random.normal(k[0], (B, T, H, hd), jnp.bfloat16)
    kp = jax.random.normal(k[1], (P, KV, ps, hd), jnp.bfloat16)
    vp = jax.random.normal(k[2], (P, KV, ps, hd), jnp.bfloat16)
    table = jnp.arange(B * 16, dtype=jnp.int32).reshape(B, 16) % (P - 1)
    lens = jnp.array([200, 100, 17, 256], jnp.int32)
    qs = lens - T
    R, V = 4, 32256
    pl_ = jax.random.normal(k[3], (B, R, V), jnp.float32)
    ql_ = jax.random.normal(k[4], (B, R, V), jnp.float32)
    toks = jnp.zeros((B, R), jnp.int32)
    u = jax.random.uniform(k[5], (B, R))
    a = jax.random.normal(k[0], (2048, 2048), jnp.bfloat16)
    mm = jax.jit(lambda x: x @ x)

    def run():
        with jax.profiler.TraceAnnotation("probe_paged"):
            o = ops.paged_attention(q, kp, vp, table, lens, qs)
            o.block_until_ready()
        with jax.profiler.TraceAnnotation("probe_verify"):
            v = ops.verify_accept_batched(pl_, ql_, toks,
                                          jnp.full((B,), R, jnp.int32), u, u)
            jax.block_until_ready(v)
        with jax.profiler.TraceAnnotation("probe_matmul"):
            mm(a).block_until_ready()
    return run


def main() -> None:
    out = sys.argv[1]
    print("devices", jax.devices(), jax.devices()[0].device_kind)
    print("memory_stats", jax.devices()[0].memory_stats())
    run = workload()
    run()                                   # compile outside the trace
    with jax.profiler.trace(out):
        run()
    path = sorted(glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    print("xplane", path, os.path.getsize(path))
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            print(f"PLANE {plane.name!r} LINE {line.name!r} n={len(evs)}")
            for name, n in names.most_common(12):
                e = next(e for e in evs if e.name == name)
                stats = {k: (str(v)[:60]) for k, v in e.stats}
                print(f"   {n:4d} {name[:80]!r} dur_ns={e.duration_ns:.0f}"
                      f" stats={stats}")


if __name__ == "__main__":
    main()
