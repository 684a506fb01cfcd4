"""Operations and bytes the algorithms need, computed from shapes.

These are the yardstick for ``mfu`` and the roofline shares: the work the
math requires, not what an implementation happens to do (pages past a
row's length, pad rows and pad lanes count for nothing).
"""
from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np

BF16 = 2


def matmul_params(m) -> int:
    """Weights a token multiplies through in one forward: every
    projection of every layer and the output head (the embedding is a
    lookup)."""
    D, H, KV, hd, F = m.d, m.heads, m.kv_heads, m.head_dim, m.ff
    per = D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F
    return m.layers * per + D * m.vocab


def forward_flops(m, ctx: Sequence[int]) -> float:
    """FLOPs of one forward over tokens whose attention spans ``ctx``
    keys each (the token's own position + 1): 2 per multiply-add through
    the weights, plus 4 * heads * head_dim per (query, key) pair for the
    scores and the weighted sum, in every layer."""
    ctx = np.asarray(ctx, np.float64)
    return float(2.0 * matmul_params(m) * len(ctx)
                 + 4.0 * m.layers * m.heads * m.head_dim * ctx.sum())


def paged_attention_cost(m, q_start: np.ndarray, lens: np.ndarray, T: int
                         ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one paged-attention call over all ``m.layers``
    layers.  Row b holds ``lens[b]`` keys, its queries sit at positions
    ``q_start[b] .. q_start[b] + T - 1``; a query at or past ``lens[b]``
    is padding.  Bytes: each row's keys and values read once (bf16), its
    real queries read and outputs written."""
    q_start = np.asarray(q_start, np.int64)
    lens = np.asarray(lens, np.int64)
    nq = np.clip(lens - q_start, 0, T)                 # real queries
    live = nq > 0
    # sum over real queries i of (q_start + i + 1) keys
    pairs = nq * (q_start + 1) + nq * (nq - 1) // 2
    flops = 4.0 * m.heads * m.head_dim * float(pairs[live].sum())
    kv = 2.0 * m.kv_heads * m.head_dim * BF16 * float(lens[live].sum())
    qo = 2.0 * m.heads * m.head_dim * BF16 * float(nq[live].sum())
    return m.layers * flops, m.layers * (kv + qo)


def verify_accept_cost(rows: int, vocab: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of the fused verify over ``rows`` real draft
    positions: read the target's and the draft's float32 logits once;
    about ten operations per vocabulary entry (two softmaxes, the ratio,
    the residual and its normalised prefix sum)."""
    return 10.0 * rows * vocab, 2.0 * 4 * rows * vocab


def least_time(flops: float, nbytes: float, peak: dict
               ) -> Tuple[float, str]:
    """The roofline's least time for one call, and which bound holds."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
