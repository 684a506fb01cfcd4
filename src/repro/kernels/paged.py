"""Pallas TPU kernel for paged KV gather (DESIGN.md §7.1).

The serving pool stores KV token-rows in fixed-size pages scattered across a
physical buffer (kv_pool.PagedStore); attention and cache-restore paths need
them contiguous.  A gather through a page table is a pure data-movement
kernel: the page table rides in SMEM via scalar prefetch, and the BlockSpec
index_map turns logical page i into physical page ``table[i]`` so each grid
step DMAs one page HBM->VMEM->HBM with no host round-trip per page.

The XLA alternative — ``buf[table]`` — materialises gather indices per
element; the Pallas version moves whole (page_size, dim) tiles, which is the
layout paged-attention kernels consume.  Grid = (n_logical_pages, dim
blocks): a swap row of a full-width model is wide (16 layers x K/V x 4096
= 131072 floats per token), and a whole (page_size, dim) f32 page, double
buffered in and out, would overflow the TPU's 16 MiB of scoped VMEM, so
the dim axis moves in lane-aligned blocks of at most ``_MAX_DIM_BLOCK``.

``valid_len`` masks the tail of a partially-filled last page to zero inside
the kernel: the free list recycles pages without scrubbing them, so a
reallocated page can still hold rows of its previous owner.  Cache-restore
after preemption reads exactly ``valid_len`` rows, and anything beyond must
be inert zeros, not a resurrected stale stream.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_LANES = 128
_MAX_DIM_BLOCK = 8192


def _dim_block(dim: int) -> int:
    """Largest lane-aligned block of at most _MAX_DIM_BLOCK that divides
    ``dim``; a dim that is not lane-aligned moves whole."""
    if dim % _LANES:
        return dim
    bd = min(dim, _MAX_DIM_BLOCK)
    while dim % bd:
        bd -= _LANES
    return bd


def _gather_kernel(table_ref, vlen_ref, pages_ref, out_ref, *, ps: int):
    # pages_ref is already the physical page selected by the index_map;
    # the body is a copy with the stale tail (rows >= valid_len) zeroed.
    del table_ref
    i = pl.program_id(0)
    row = i * ps + jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    out_ref[...] = jnp.where(row < vlen_ref[0], pages_ref[...], 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_gather(pages: jax.Array, table: jax.Array,
                 valid_len: Optional[jax.Array] = None, *,
                 interpret: bool = True) -> jax.Array:
    """Gather logical pages from a paged buffer.

    pages: (num_physical_pages, page_size, dim) paged storage.
    table: (n,) int32 physical page id per logical page.
    valid_len: optional scalar — rows at positions >= valid_len are zeroed
        (stale remnants of a page's previous owner).  Default: keep all.
    Returns (n * page_size, dim) contiguous rows.
    """
    P, ps, dim = pages.shape
    n = table.shape[0]
    if valid_len is None:
        valid_len = n * ps
    vlen = jnp.asarray(valid_len, jnp.int32).reshape(1)
    bd = _dim_block(dim)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n, dim // bd),
        in_specs=[pl.BlockSpec((1, ps, bd),
                               lambda i, d, t, vl: (t[i], 0, d))],
        out_specs=pl.BlockSpec((1, ps, bd), lambda i, d, t, vl: (i, 0, d)),
    )
    out = pl.pallas_call(
        functools.partial(_gather_kernel, ps=ps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, ps, dim), pages.dtype),
        interpret=interpret,
    )(table.astype(jnp.int32), vlen, pages)
    return out.reshape(n * ps, dim)
