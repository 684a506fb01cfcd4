"""Pallas TPU kernel for fused speculative-decoding verification.

For each draft position r with target logits p_l (V,), draft logits q_l (V,),
drafted token t_r and uniform u_r, computes in one VMEM-resident pass:

  * softmax probabilities p, q (f32, numerically-stable two-sided),
  * accept_r     = u_r <= p[t_r] / q[t_r]            (Leviathan criterion)
  * residual_r   ~ norm(max(0, p - q))               (inverse-CDF sample
                   using a second uniform w_r)
  * p_tok, q_tok = p[t_r], q[t_r]

The naive implementation round-trips the (R, V) logits through HBM four
times (max, sum, gather, residual); this kernel reads them once.  V up to
~1M fits the full-row-in-VMEM strategy (two f32 rows = 8 MB at V=1M);
larger vocabularies would stream V blocks with the same accumulators (the
assigned configs top out at 262k).

Layout for the TPU lowering (DESIGN.md §7.7):

  * grid (B, R), one program per (row, draft position); the per-row draft
    lengths, drafted tokens and both uniforms ride in SMEM via scalar
    prefetch, so ragged rows (different gamma per request — H-RAD's
    adaptive stop) mask their pad positions for free.  Masked positions
    return accept = 0, residual = 0, p_tok = q_tok = 0;
  * each vocabulary row is padded to a multiple of ``_TILE`` with -inf
    logits (zero probability) and presented as (V / 128, 128) tiles, so a
    block's last two dims are whole dims of the array;
  * p[t] is a one-hot select over a flat-index iota (no in-kernel gather);
  * the residual CDF is a prefix sum written as two triangular matmuls —
    inclusive within each 128-lane tile, plus the running total of the
    tiles before it — since the lowering has no cumsum;
  * the four results of a row are written into (1, R) output blocks that
    stay resident across the row's R grid steps, one lane per step.

``verify_accept`` (one request, (R, V)) is the batched kernel at B = 1
with every position valid.

``verify_accept_batched_xla`` is the same contract as a pure-XLA jitted
function (an online max/sum pass, no pallas) — the compiled backend of
``ops.verify_accept_batched`` off-TPU.  The serving loop routes per
``device_loop.kernel_route``: through the pallas kernel on TPU, and
through the probs-space twin ``sampling.verify_chain_device`` off-TPU
(same math as the XLA path here; both are pinned against the numpy cores
and against each other in tests/test_verify_device.py).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# vocab padding unit: 8 sublanes x 128 lanes, so the tile-count axis
# (V / 128) is a whole number of sublane groups for the prefix matmul
_TILE = 8 * _LANES
_HI = jax.lax.Precision.HIGHEST


def _total(x):
    """Sum of a 2-D tile as a (1, 1) array (reductions stay 2-D)."""
    return x.sum(axis=1, keepdims=True).sum(axis=0, keepdims=True)


def _softmax(x):
    m = x.max(axis=1, keepdims=True).max(axis=0, keepdims=True)
    e = jnp.exp(x - m)
    return e / _total(e)


def _prefix_sum(r):
    """Inclusive prefix sum of ``r`` (nT, 128) in flat row-major order."""
    nT = r.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)
    upper = (i <= j).astype(jnp.float32)
    within = jnp.dot(r, upper, precision=_HI,
                     preferred_element_type=jnp.float32)
    a = jax.lax.broadcasted_iota(jnp.int32, (nT, nT), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (nT, nT), 1)
    before = (c < a).astype(jnp.float32)
    tiles_before = jnp.dot(before, r, precision=_HI,
                           preferred_element_type=jnp.float32)
    return within + tiles_before.sum(axis=1, keepdims=True)


def _batched_kernel(lens_ref, tok_ref, u_ref, w_ref, p_ref, q_ref,
                    acc_ref, res_ref, ptok_ref, qtok_ref, *, vocab: int):
    b = pl.program_id(0)
    r = pl.program_id(1)
    valid = r < lens_ref[b]
    p = _softmax(p_ref[0, 0].astype(jnp.float32))     # (nT, 128)
    q = _softmax(q_ref[0, 0].astype(jnp.float32))
    shape = p.shape
    idx = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * _LANES
           + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    hit = idx == tok_ref[b, r]
    p_t = jnp.where(valid, _total(jnp.where(hit, p, 0.0)), 0.0)   # (1, 1)
    q_t = jnp.where(valid, _total(jnp.where(hit, q, 0.0)), 0.0)
    acc = (valid & (u_ref[b, r] <= p_t / jnp.maximum(q_t, 1e-30))
           ).astype(jnp.int32)
    res = jnp.maximum(p - q, 0.0)
    z = _total(res)
    # fall back to p when the residual is (numerically) empty
    res = jnp.where(z > 1e-12, res / jnp.maximum(z, 1e-30), p)
    cdf = _prefix_sum(res)
    # renormalize by the last real cdf entry (f32 sums can top out below
    # any uniform in (cdf[V-1], 1)) and clamp — never emit token id V
    last = _total(jnp.where(idx == vocab - 1, cdf, 0.0))
    cdf = cdf / jnp.maximum(last, 1e-30)
    below = (idx < vocab) & (cdf <= w_ref[b, r])
    tok = jnp.minimum(_total(below.astype(jnp.int32)), vocab - 1)
    tok = jnp.where(valid, tok, 0)

    lane = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape[1:], 1)
    for ref, val in ((acc_ref, acc), (res_ref, tok), (ptok_ref, p_t),
                     (qtok_ref, q_t)):
        ref[0] = jnp.where(lane == r, val, ref[0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def verify_accept_batched(p_logits: jax.Array, q_logits: jax.Array,
                          tokens: jax.Array, lens: jax.Array,
                          uniforms: jax.Array, res_uniforms: jax.Array, *,
                          interpret: bool = True
                          ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                     jax.Array]:
    """Fused batched verification with ragged rows.

    p_logits, q_logits: (B, R, V); tokens/uniforms/res_uniforms: (B, R);
    lens: (B,) valid draft positions per row (positions >= lens[b] are
    masked to zeros).  Returns (accept (B, R) i32, residual_tokens (B, R)
    i32, p_tok (B, R) f32, q_tok (B, R) f32).
    """
    B, R, V = p_logits.shape
    pad = (-V) % _TILE
    nT = (V + pad) // _LANES

    def tiles(x):
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad)), constant_values=-1e30)
        return x.reshape(B, R, nT, _LANES)

    row = pl.BlockSpec((1, 1, nT, _LANES), lambda b, r, *_: (b, r, 0, 0))
    out = pl.BlockSpec((1, 1, R), lambda b, r, *_: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, R),
        in_specs=[row, row],
        out_specs=[out, out, out, out],
    )
    shapes = [jax.ShapeDtypeStruct((B, 1, R), dt) for dt in
              (jnp.int32, jnp.int32, jnp.float32, jnp.float32)]
    outs = pl.pallas_call(
        functools.partial(_batched_kernel, vocab=V),
        grid_spec=grid_spec,
        out_shape=shapes,
        interpret=interpret,
    )(lens.astype(jnp.int32), tokens.astype(jnp.int32),
      uniforms.astype(jnp.float32), res_uniforms.astype(jnp.float32),
      tiles(p_logits), tiles(q_logits))
    return tuple(o[:, 0] for o in outs)


def verify_accept(p_logits: jax.Array, q_logits: jax.Array,
                  tokens: jax.Array, uniforms: jax.Array,
                  res_uniforms: jax.Array, *, interpret: bool = True
                  ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused verification of one request.

    p_logits, q_logits: (R, V); tokens, uniforms, res_uniforms: (R,).
    Returns (accept (R,) int32, residual_tokens (R,) int32,
             p_tok (R,) f32, q_tok (R,) f32).
    """
    R = p_logits.shape[0]
    outs = verify_accept_batched(
        p_logits[None], q_logits[None], tokens[None],
        jnp.full((1,), R, jnp.int32), uniforms[None], res_uniforms[None],
        interpret=interpret)
    return tuple(o[0] for o in outs)


@jax.jit
def verify_accept_batched_xla(p_logits: jax.Array, q_logits: jax.Array,
                              tokens: jax.Array, lens: jax.Array,
                              uniforms: jax.Array, res_uniforms: jax.Array
                              ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                         jax.Array]:
    """Compiled (non-pallas) path, same contract as verify_accept_batched.

    Written as an explicit online max-subtract/exp-sum pass (rather than
    two jax.nn.softmax calls) so the XLA path and the ref.py oracle stay
    algorithmically independent.
    """
    B, R, V = p_logits.shape
    pl_ = p_logits.astype(jnp.float32)
    ql_ = q_logits.astype(jnp.float32)
    pm = pl_.max(-1, keepdims=True)
    qm = ql_.max(-1, keepdims=True)
    pe = jnp.exp(pl_ - pm)
    qe = jnp.exp(ql_ - qm)
    p = pe / pe.sum(-1, keepdims=True)
    q = qe / qe.sum(-1, keepdims=True)
    t = tokens.astype(jnp.int32)[..., None]
    valid = (jnp.arange(R, dtype=jnp.int32)[None]
             < lens.astype(jnp.int32)[:, None])
    p_t = jnp.where(valid, jnp.take_along_axis(p, t, -1)[..., 0], 0.0)
    q_t = jnp.where(valid, jnp.take_along_axis(q, t, -1)[..., 0], 0.0)
    acc = (valid & (uniforms.astype(jnp.float32)
                    <= p_t / jnp.maximum(q_t, 1e-30))).astype(jnp.int32)
    r = jnp.maximum(p - q, 0.0)
    z = r.sum(-1, keepdims=True)
    r = jnp.where(z > 1e-12, r / jnp.maximum(z, 1e-30), p)
    cdf = jnp.cumsum(r, axis=-1)
    cdf = cdf / jnp.maximum(cdf[..., -1:], 1e-30)     # see _kernel
    res = jnp.sum((cdf <= res_uniforms.astype(jnp.float32)[..., None])
                  .astype(jnp.int32), axis=-1)
    res = jnp.where(valid, jnp.minimum(res, V - 1), 0)
    return acc, res, p_t, q_t
