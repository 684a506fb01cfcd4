"""Plain float32 reference of a LLaMA-architecture decoder (DeepSeek-Coder).

Published math (Touvron et al. 2023; the ``LlamaForCausalLM`` config):
token embedding, then per layer ``x += Wo·attn(rope(Wq·n1(x)),
rope(Wk·n1(x)), Wv·n1(x))`` with causal softmax attention, grouped KV
heads (query head i reads KV head i // (H / KV)) and rotate-half RoPE at
``rope_theta``, then ``x += Wd·(silu(Wg·n2(x)) * Wu·n2(x))``; a final
RMSNorm and the output head.  Departure, shared with the program: no
linear RoPE scaling.  The weights arrive in the served layout of
``harness/weights.py``: the embedding stored divided by sqrt(d), each
norm scale stored minus one; both are undone here.

Every matmul runs at ``Precision.HIGHEST`` (true float32 on a TPU), one
layer at a time and attention in blocks of query rows, so a long sequence
fits beside the weights.  ``quant="int8"`` is the control: the same
forward with every matmul operand quantized to int8 (weights per output
channel, activations per row), the precision a later change could be
tempted to serve in.  Imports nothing of the program.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
QB = 512                     # query rows per attention block; pad unit


def _q8(a: jax.Array, axis: int) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 quantization along ``axis``: (int8 values, float32
    scale)."""
    s = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True)
                    .astype(jnp.float32), 1e-30) / 127.0
    q = jnp.clip(jnp.round(a.astype(jnp.float32) / s), -127, 127)
    return q.astype(jnp.int8), s


def _mm(x: jax.Array, w: jax.Array, quant: Optional[str]) -> jax.Array:
    if quant == "int8":
        (xq, sx), (wq, sw) = _q8(x, -1), _q8(w, 0)
        acc = jax.lax.dot(xq, wq, preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32) * sx * sw
    return jnp.matmul(x, w.astype(jnp.float32), precision=HI)


def _rms(x: jax.Array, scale_minus_one: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale_minus_one.astype(
        jnp.float32))


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x: (T, heads, hd); rotate-half RoPE at positions 0..T-1."""
    T, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    s, c = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _attention(q, k, v):
    """Causal attention in blocks of QB query rows.  q (T, H, hd), k/v
    (T, KV, hd) -> (T, H * hd)."""
    T, H, hd = q.shape
    KV = k.shape[1]
    qg = q.reshape(T // QB, QB, KV, H // KV, hd)
    kpos = jnp.arange(T)

    def block(args):
        i, qb = args
        s = jnp.einsum("qkgh,tkh->kgqt", qb, k, precision=HI) / math.sqrt(hd)
        qpos = i * QB + jnp.arange(QB)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkh->qkgh", p, v,
                          precision=HI).reshape(QB, H * hd)

    out = jax.lax.map(block, (jnp.arange(T // QB), qg))
    return out.reshape(T, H * hd)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _layer(p, x, *, m, quant):
    T = x.shape[0]
    H, KV, hd = m.heads, m.kv_heads, m.head_dim
    h = _rms(x, p["mixer"]["ln"], m.eps)
    q = _rope(_mm(h, p["mixer"]["wq"], quant).reshape(T, H, hd), m.theta)
    k = _rope(_mm(h, p["mixer"]["wk"], quant).reshape(T, KV, hd), m.theta)
    v = _mm(h, p["mixer"]["wv"], quant).reshape(T, KV, hd)
    x = x + _mm(_attention(q, k, v), p["mixer"]["wo"], quant)
    h = _rms(x, p["ffn"]["ln"], m.eps)
    g = _mm(h, p["ffn"]["wg"], quant)
    u = _mm(h, p["ffn"]["wu"], quant)
    x = x + _mm(g * jax.nn.sigmoid(g) * u, p["ffn"]["wd"], quant)
    return x, k, v


@functools.partial(jax.jit, static_argnames=("m",))
def _embed(embed, tokens, *, m):
    return embed[tokens].astype(jnp.float32) * math.sqrt(m.d)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _head(params, x, *, m, quant):
    h = _rms(x, params["final_norm"], m.eps)
    return _mm(h, params["lm_head"], quant)


def forward(params: Dict[str, Any], m, tokens: np.ndarray, *,
            quant: Optional[str] = None
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Logits (T, V) float32 at every position of ``tokens`` (T,), and
    the last layer's post-RoPE keys and values (T, KV, hd).  Runs layer
    by layer; the sequence is padded to a multiple of QB at the end
    (causal, so the padding changes no real position)."""
    T = len(tokens)
    Tp = -(-T // QB) * QB
    toks = np.zeros(Tp, np.int32)
    toks[:T] = tokens
    x = _embed(params["embed"], jnp.asarray(toks), m=m)
    stack = params["blocks"][0]
    k = v = None
    for i in range(m.layers):
        p = jax.tree.map(lambda a: a[i], stack)
        x, k, v = _layer(p, x, m=m, quant=quant)
    return _head(params, x, m=m, quant=quant)[:T], k[:T], v[:T]
