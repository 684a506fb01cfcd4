"""Pallas TPU paged-attention decode kernel (DESIGN.md §7.5).

Attention that consumes the serving pool's page tables *directly*: K/V live
scattered across fixed-size pages of a physical buffer (kv_pool page ids)
and are never gathered into dense per-row caches.  This is what makes
rollback-aware page reclamation physically free — a rejected branch's pages
go back to the free list with zero copies, and the winning branch's table
is adopted instead of its KV being memcpy'd.

Layout and grid:

  * q:        (B, T, H, hd)  — T decode/verify tokens per row (T is small:
              pending + chunk, <= gamma + 2), pre-arranged to
              (B, KV, G, Tp, hd) with G = H // KV query groups;
  * k_pages / v_pages: (P, KV, page_size, hd) physical paged buffers,
              head-major so that one head's page is a whole (page_size, hd)
              tile — the TPU lowering requires a block's last two dims to
              be (8, 128)-aligned or full, which a (page_size, 1, hd)
              slice of a token-major page is not once KV > 1; the last
              physical page is the serving layer's trash page and is
              never referenced by a live table entry;
  * table:    (B, n_max) int32 page table — entry j holds the physical page
              of logical page j; rows with fewer pages pad with the trash
              page id (the tail-page mask makes the value irrelevant);
  * lens:     (B,) int32 valid KV length per row INCLUDING the T query
              tokens (the engine extends the pool before the forward, so
              the pool length is exactly this);
  * q_start:  (B,) int32 absolute position of q[:, 0].

Grid = (B, KV, n_max) with the page axis innermost: the page table rides in
SMEM via scalar prefetch and the k/v BlockSpec index_map sends grid step
(b, h, j) to head ``h`` of physical page ``table[b, j]``, so each step
DMAs one contiguous (page_size, hd) tile straight from its scattered
location.  The
(m, l, acc) online-softmax state lives in VMEM scratch across page steps;
partial tail pages and pages beyond a row's count are masked by position
(kpos >= lens[b]), exactly like the dense kernel masks unwritten cache
slots.  Per-row sequence lengths make the batch axis ragged for free: a
short row's trailing page steps are fully masked no-ops.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(table_ref, lens_ref, qstart_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *,
            page_size: int, n_pages: int, window: int,
            cap: Optional[float], scale: float):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (G, Tp, hd)
    k = k_ref[0, 0].astype(jnp.float32)                  # (ps, hd)
    logits = jax.lax.dot_general(
        q, k, (((2,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # (G, Tp, ps)
    if cap is not None:
        logits = cap * jnp.tanh(logits / cap)

    Tp = q.shape[1]
    kpos = (j * page_size
            + jax.lax.broadcasted_iota(jnp.int32, (Tp, page_size), 1))
    qpos = (qstart_ref[b]
            + jax.lax.broadcasted_iota(jnp.int32, (Tp, page_size), 0))
    mask = (kpos < lens_ref[b]) & (kpos <= qpos)
    if window > 0:
        mask &= (qpos - kpos) < window
    logits = jnp.where(mask[None], logits, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=-1))
    # a fully-masked page leaves m_new at NEG_INF and exp(0) would leak
    # unit mass per masked slot — zero it under the mask instead
    p = jnp.where(mask[None], jnp.exp(logits - m_new[..., None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
    v = v_ref[0, 0].astype(jnp.float32)                  # (ps, hd)
    pv = jax.lax.dot_general(p, v, (((2,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * corr[..., None] + pv
    m_scr[...] = m_new

    @pl.when(j == n_pages - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0, 0] = (acc_scr[...] / l[..., None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "cap"))
def paged_decode_attention_xla(q: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array, table: jax.Array,
                               lens: jax.Array, q_start: jax.Array, *,
                               window: int = 0,
                               cap: Optional[float] = None) -> jax.Array:
    """Pure-XLA twin of ``paged_decode_attention`` (same shapes/masking).

    Gathers the row's pages dense through the table, then runs masked
    attention — unlike the Pallas kernel this is ordinary HLO, so GSPMD can
    partition it: under the serving mesh (DESIGN.md §7.10) the page buffers
    shard over the KV-head (else head_dim) axis, the gather indexes the
    *unsharded* page axis, and the whole computation stays collective-free
    per head shard.  The mesh serving path routes here off-TPU; the Pallas
    kernel remains the single-device/TPU fast path (its custom-call cannot
    be SPMD-partitioned).
    """
    B, T, H, hd = q.shape
    _P, KV, ps, _ = k_pages.shape
    n_max = table.shape[1]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    S = n_max * ps

    # (B, n_max, KV, ps, hd) -> (B, S, KV, hd); logical key position of
    # table slot (j, o) is j*ps + o, matching the kernel's kpos iota.
    def dense(pages):
        g = pages[table].transpose(0, 1, 3, 2, 4)
        return g.reshape(B, S, KV, hd).astype(jnp.float32)

    k, v = dense(k_pages), dense(v_pages)
    qr = q.reshape(B, T, KV, G, hd).astype(jnp.float32) * scale

    logits = jnp.einsum("btkgh,bskh->bkgts", qr, k)      # (B, KV, G, T, S)
    if cap is not None:
        logits = cap * jnp.tanh(logits / cap)

    kpos = jnp.arange(S, dtype=jnp.int32)
    qpos = q_start[:, None] + jnp.arange(T, dtype=jnp.int32)   # (B, T)
    mask = ((kpos[None, None] < lens[:, None, None])
            & (kpos[None, None] <= qpos[:, :, None]))          # (B, T, S)
    if window > 0:
        mask &= (qpos[:, :, None] - kpos[None, None]) < window
    maskb = mask[:, None, None]                                # (B,1,1,T,S)
    logits = jnp.where(maskb, logits, NEG_INF)

    m = logits.max(axis=-1)
    p = jnp.where(maskb, jnp.exp(logits - m[..., None]), 0.0)
    l = jnp.maximum(p.sum(axis=-1), 1e-20)
    o = jnp.einsum("bkgts,bskh->bkgth", p, v) / l[..., None]
    return o.transpose(0, 3, 1, 2, 4).reshape(B, T, H, hd).astype(q.dtype)


def _pad_q(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit,
                   static_argnames=("window", "cap", "interpret"))
def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, table: jax.Array,
                           lens: jax.Array, q_start: jax.Array, *,
                           window: int = 0, cap: Optional[float] = None,
                           interpret: bool = True) -> jax.Array:
    """Decode attention over physically paged KV through a page table.

    Shapes as in the module docstring.  Returns (B, T, H, hd).
    """
    B, T, H, hd = q.shape
    P, KV, ps, _ = k_pages.shape
    n_max = table.shape[1]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)

    qr = q.reshape(B, T, KV, G, hd).transpose(0, 2, 3, 1, 4)
    qr = _pad_q(qr, 3, 8)                                # (B, KV, G, Tp, hd)
    Tp = qr.shape[3]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV, n_max),
        in_specs=[
            pl.BlockSpec((1, 1, G, Tp, hd),
                         lambda b, h, j, tbl, ln, qs: (b, h, 0, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd),
                         lambda b, h, j, tbl, ln, qs: (tbl[b, j], h, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd),
                         lambda b, h, j, tbl, ln, qs: (tbl[b, j], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, G, Tp, hd), lambda b, h, j, tbl, ln, qs: (b, h, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, Tp), jnp.float32),
            pltpu.VMEM((G, Tp), jnp.float32),
            pltpu.VMEM((G, Tp, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel, page_size=ps, n_pages=n_max, window=window, cap=cap,
        scale=scale)
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, Tp, hd), q.dtype),
        interpret=interpret,
    )(table.astype(jnp.int32), lens.astype(jnp.int32),
      q_start.astype(jnp.int32), qr, k_pages, v_pages)
    return o[:, :, :, :T].transpose(0, 3, 1, 2, 4).reshape(B, T, H, hd)
