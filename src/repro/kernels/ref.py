"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def attention_ref(q, k, v, q_pos, k_pos, *, causal=True, window=0,
                  cap: Optional[float] = None):
    """Naive full-matrix attention.  Shapes as kernels.flash_attention
    (k may have batch 1 with q batch B — broadcast)."""
    B, T, H, hd = q.shape
    Bk, S, KV, _ = k.shape
    G = H // KV
    if Bk == 1 and B > 1:
        k = jnp.broadcast_to(k, (B,) + k.shape[1:])
        v = jnp.broadcast_to(v, (B,) + v.shape[1:])
        k_pos = jnp.broadcast_to(k_pos, (B, S))
    qf = q.astype(jnp.float32).reshape(B, T, KV, G, hd)
    logits = jnp.einsum("btkgh,bskh->bkgts", qf, k.astype(jnp.float32))
    logits /= math.sqrt(hd)
    if cap is not None:
        logits = cap * jnp.tanh(logits / cap)
    mask = (k_pos >= 0)[:, None, None, None, :]
    if causal:
        mask &= k_pos[:, None, None, None, :] <= q_pos[:, None, None, :, None]
    if window > 0:
        mask &= (q_pos[:, None, None, :, None]
                 - k_pos[:, None, None, None, :]) < window
    logits = jnp.where(mask, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgts,bskh->btkgh", w, v.astype(jnp.float32))
    return out.reshape(B, T, H, hd).astype(q.dtype)


def branch_decode_ref(q, prefix_k, prefix_v, prefix_pos,
                      suffix_k, suffix_v, suffix_pos, q_pos, *,
                      cap: Optional[float] = None):
    """Oracle for the shared-prefix branch decode: concatenate the broadcast
    prefix with the per-branch suffix and run naive attention."""
    kb = q.shape[0]
    k = jnp.concatenate(
        [jnp.broadcast_to(prefix_k, (kb,) + prefix_k.shape[1:]), suffix_k],
        axis=1)
    v = jnp.concatenate(
        [jnp.broadcast_to(prefix_v, (kb,) + prefix_v.shape[1:]), suffix_v],
        axis=1)
    kp = jnp.concatenate(
        [jnp.broadcast_to(prefix_pos, (kb,) + prefix_pos.shape[1:]),
         suffix_pos], axis=1)
    return attention_ref(q, k, v, q_pos, kp, causal=True, cap=cap)


def paged_attention_ref(q, k_pages, v_pages, table, lens, q_start, *,
                        window=0, cap: Optional[float] = None):
    """Oracle for the paged decode kernel: gather every row's pages to a
    dense (S, KV, hd) cache, mark slots beyond the row's length invalid
    (-1), and run naive attention.  Shapes as kernels.paged_attention."""
    B, T, _, _ = q.shape
    _, KV, ps, hd = k_pages.shape
    S = table.shape[1] * ps
    k = k_pages[table].transpose(0, 1, 3, 2, 4).reshape(B, S, KV, hd)
    v = v_pages[table].transpose(0, 1, 3, 2, 4).reshape(B, S, KV, hd)
    kpos = jnp.arange(S, dtype=jnp.int32)[None]
    kpos = jnp.where(kpos < lens[:, None], kpos, -1)
    qpos = q_start[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    return attention_ref(q, k, v, qpos, kpos, causal=True, window=window,
                         cap=cap)


def ssm_scan_ref(x, dt, Bm, Cm, A, D, h0, *, return_states: bool = False
                 ) -> Tuple[jax.Array, ...]:
    """Sequential selective scan (matches models.layers.mamba math).

    With ``return_states`` additionally returns hs (B, T, E, N): the
    post-step carry after every position (rollback-checkpoint oracle)."""
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    decay = jnp.exp(dtf[..., None] * A.astype(jnp.float32))   # (B,T,E,N)
    drive = (dtf * xf)[..., None] * Bm.astype(jnp.float32)[:, :, None, :]

    def step(h, xs):
        d_t, u_t = xs
        h = d_t * h + u_t
        return h, h

    hT, hs = jax.lax.scan(
        step, h0.astype(jnp.float32),
        (decay.transpose(1, 0, 2, 3), drive.transpose(1, 0, 2, 3)))
    hs = hs.transpose(1, 0, 2, 3)
    y = jnp.einsum("bten,btn->bte", hs, Cm.astype(jnp.float32)) \
        + D.astype(jnp.float32) * xf
    if return_states:
        return y, hT, hs
    return y, hT


def verify_accept_batched_ref(p_logits, q_logits, tokens, lens, uniforms,
                              res_uniforms):
    """Oracle for the batched (B, R, V) verification grid: per-row lens
    masking (positions >= lens[b] return zeros), otherwise the per-row
    verify_accept semantics."""
    p = jax.nn.softmax(p_logits.astype(jnp.float32), axis=-1)
    q = jax.nn.softmax(q_logits.astype(jnp.float32), axis=-1)
    B, R, _ = p.shape
    valid = jnp.arange(R)[None] < lens[:, None]
    t = tokens.astype(jnp.int32)[..., None]
    p_t = jnp.where(valid, jnp.take_along_axis(p, t, -1)[..., 0], 0.0)
    q_t = jnp.where(valid, jnp.take_along_axis(q, t, -1)[..., 0], 0.0)
    accept = (valid & (uniforms <= p_t / jnp.maximum(q_t, 1e-30))
              ).astype(jnp.int32)
    r = jnp.maximum(p - q, 0.0)
    z = r.sum(-1, keepdims=True)
    r = jnp.where(z > 1e-12, r / jnp.maximum(z, 1e-30), p)
    cdf = jnp.cumsum(r, axis=-1)
    # renormalized + clamped like the kernel: f32 cumsum can end below a
    # uniform in (cdf[-1], 1), which must not emit token id V
    cdf = cdf / jnp.maximum(cdf[..., -1:], 1e-30)
    res = jnp.sum((cdf <= res_uniforms[..., None]).astype(jnp.int32), axis=-1)
    res = jnp.minimum(res, p.shape[-1] - 1)
    return accept, jnp.where(valid, res, 0), p_t, q_t


def verify_accept_ref(p_logits, q_logits, tokens, uniforms, res_uniforms):
    p = jax.nn.softmax(p_logits.astype(jnp.float32), axis=-1)
    q = jax.nn.softmax(q_logits.astype(jnp.float32), axis=-1)
    R = p.shape[0]
    idx = jnp.arange(R)
    p_t = p[idx, tokens]
    q_t = q[idx, tokens]
    accept = (uniforms <= p_t / jnp.maximum(q_t, 1e-30)).astype(jnp.int32)
    r = jnp.maximum(p - q, 0.0)
    z = r.sum(-1, keepdims=True)
    r = jnp.where(z > 1e-12, r / jnp.maximum(z, 1e-30), p)
    cdf = jnp.cumsum(r, axis=-1)
    cdf = cdf / jnp.maximum(cdf[..., -1:], 1e-30)
    res = jnp.sum((cdf <= res_uniforms[:, None]).astype(jnp.int32), axis=-1)
    res = jnp.minimum(res, p.shape[-1] - 1)
    return accept, res, p_t, q_t
