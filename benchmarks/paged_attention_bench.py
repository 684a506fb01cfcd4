"""Paged-attention bytes-moved sweep: page size x batch x seq len, JSON.

Quantifies what the paged decode path (kernels/paged_attention.py) saves
over the gather path it replaced.  Per verify step the two paths are:

  * gather path — ``paged_gather`` every row's pages into a dense
    contiguous cache, then run dense ``flash_attention`` over the padded
    (B, S_max) batch: the pages are read once, the dense copy is written
    once and read again, and padding makes every row pay the longest row's
    KV traffic;
  * paged path — ``paged_attention`` attends in place through the page
    table: the pages are read exactly once and nothing is written back.
    The kernel grid covers the padded table width, so a short row's
    trailing (masked) table slots are still DMA'd — compute no-ops, not
    DMA no-ops — and the accounting below charges the paged path for
    them honestly.

Both paths run on the same fragmented layout (pages allocated round-robin
across rows, so tables are interleaved like a live pool) and the outputs
are checked allclose before any number is reported.  Bytes are accounted
analytically from the shapes — wall clock in interpret mode measures the
Python interpreter, not the DMA engine, and is reported only as a sanity
column.  The paged path must move strictly fewer bytes in every cell; the
run fails loudly if it ever does not.

Usage:
  PYTHONPATH=src python benchmarks/paged_attention_bench.py \
      --out paged_attention_sweep.json
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import ops  # noqa: E402

ITEM = 4            # float32 bytes
KV, HD, G = 2, 32, 2      # KV heads, head dim, query groups (H = KV * G)


def fragmented_layout(rng, batch, seq_lens, ps):
    """Round-robin page allocation across rows — tables interleave in the
    physical buffer exactly like streams growing together in a live pool."""
    n_pages = [int(-(-s // ps)) for s in seq_lens]
    n_max = max(n_pages)
    P = sum(n_pages)
    order = [b for j in range(n_max) for b in range(batch) if j < n_pages[b]]
    perm = rng.permutation(P)          # scatter physically, too
    table = np.full((batch, n_max), P, np.int32)      # pad = trash page
    cursor = {b: 0 for b in range(batch)}
    for phys, b in zip(perm, order):
        table[b, cursor[b]] = phys
        cursor[b] += 1
    return table, P


def bytes_moved(batch, seq_lens, ps, T):
    """Analytic HBM traffic per verify step (K + V, q/out identical in both
    paths and excluded).  Gather: read the live pages, write the dense
    copy, read it back at the padded batch length.  Paged: one page-tile
    read per (row, table slot) — the grid is (B, KV, n_max), so padded
    slots of short ragged rows are charged too (masked steps still DMA).
    The paged path therefore wins by exactly the gather round-trip:
    gather = paged + 2 * live_page_bytes."""
    n_pages = [int(-(-s // ps)) for s in seq_lens]
    n_max = max(n_pages)
    live_page_bytes = 2 * sum(n_pages) * ps * KV * HD * ITEM   # K and V
    padded_read = 2 * batch * n_max * ps * KV * HD * ITEM
    return {
        "gather": 2 * live_page_bytes + padded_read,
        "paged": padded_read,
    }


def run_cell(rng, batch, seq_len, ps, T):
    # ragged lens around seq_len so per-row masking is exercised
    seq_lens = [max(T + 1, seq_len - int(rng.integers(0, seq_len // 2 + 1)))
                for _ in range(batch)]
    seq_lens[0] = seq_len
    table, P = fragmented_layout(rng, batch, seq_lens, ps)
    H = KV * G
    kp = jnp.asarray(rng.normal(size=(P + 1, KV, ps, HD)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P + 1, KV, ps, HD)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(batch, T, H, HD)), jnp.float32)
    lens = np.asarray(seq_lens, np.int32)
    q_start = lens - T

    # --- gather path: pages -> dense rows -> dense flash attention
    t0 = time.time()
    smax = table.shape[1] * ps
    dim = KV * HD
    dense_k = np.zeros((batch, smax, KV, HD), np.float32)
    dense_v = np.zeros((batch, smax, KV, HD), np.float32)
    # paged_gather moves token-major (ps, dim) pages; the pool is head-major
    kp_tok = kp.transpose(0, 2, 1, 3).reshape(P + 1, ps, dim)
    vp_tok = vp.transpose(0, 2, 1, 3).reshape(P + 1, ps, dim)
    for b in range(batch):
        npg = int(-(-int(lens[b]) // ps))        # gather live pages only
        dense_k[b, :npg * ps] = np.asarray(
            ops.paged_gather(kp_tok, table[b, :npg],
                             int(lens[b]))).reshape(npg * ps, KV, HD)
        dense_v[b, :npg * ps] = np.asarray(
            ops.paged_gather(vp_tok, table[b, :npg],
                             int(lens[b]))).reshape(npg * ps, KV, HD)
    kpos = np.where(np.arange(smax)[None] < lens[:, None],
                    np.arange(smax)[None], -1)
    qpos = q_start[:, None] + np.arange(T)[None]
    out_gather = ops.flash_attention(q, jnp.asarray(dense_k),
                                     jnp.asarray(dense_v),
                                     jnp.asarray(qpos), jnp.asarray(kpos),
                                     bq=16, bk=16)
    wall_gather = time.time() - t0

    # --- paged path: attend in place through the table
    t0 = time.time()
    out_paged = ops.paged_attention(q, kp, vp, table, lens, q_start)
    wall_paged = time.time() - t0

    err = float(jnp.max(jnp.abs(out_gather - out_paged)))
    assert err < 2e-4, f"paths diverge: max abs err {err}"
    nb = bytes_moved(batch, seq_lens, ps, T)
    assert nb["paged"] < nb["gather"], (nb, batch, seq_len, ps)
    return {
        "page_size": ps, "batch": batch, "seq_len": seq_len,
        "verify_tokens": T, "seq_lens": seq_lens,
        "bytes_gather": nb["gather"], "bytes_paged": nb["paged"],
        "bytes_ratio": nb["paged"] / nb["gather"],
        "wall_gather_s": wall_gather, "wall_paged_s": wall_paged,
        "max_abs_err": err,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--page-sizes", type=int, nargs="+", default=[8, 16])
    ap.add_argument("--batches", type=int, nargs="+", default=[2, 4])
    ap.add_argument("--seq-lens", type=int, nargs="+", default=[64, 128])
    ap.add_argument("--verify-tokens", type=int, default=5,
                    help="q tokens per row (pending + chunk of one round)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None, help="JSON report path")
    args = ap.parse_args()

    rng = np.random.default_rng(args.seed)
    cells = []
    for ps, batch, s in itertools.product(args.page_sizes, args.batches,
                                          args.seq_lens):
        cell = run_cell(rng, batch, s, ps, args.verify_tokens)
        cells.append(cell)
        print(f"ps={ps:3d} B={batch} S={s:5d}: "
              f"{cell['bytes_paged'] / 1e3:8.1f} kB paged vs "
              f"{cell['bytes_gather'] / 1e3:8.1f} kB gather "
              f"(x{cell['bytes_gather'] / cell['bytes_paged']:.2f} less, "
              f"err {cell['max_abs_err']:.1e})")
    report = {
        "kind": "paged_attention_bytes_sweep",
        "kv_heads": KV, "head_dim": HD, "query_groups": G,
        "sweep": cells,
        "paged_always_fewer_bytes": all(
            c["bytes_paged"] < c["bytes_gather"] for c in cells),
    }
    assert report["paged_always_fewer_bytes"]
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
        print(f"report written to {args.out}")


if __name__ == "__main__":
    main()
