"""Seeded bf16 weights, made on the device in one jitted call per model.

The arrays follow the served program's parameter layout (a LLaMA block
stack stored as one stacked tree), in the parameterization the program
computes with: the embedding table holds the published embedding divided
by sqrt(d), and each RMSNorm stores its scale minus one.  The reference
(``references/llama.py``) reads the same arrays and undoes both.  Scales:
N(0, 1/fan_in) for every projection, N(0, 1/d) for the embedding and the
head, N(0, 0.05^2) for the norm offsets, so the norms are exercised.
Nothing here imports the program.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Dims(NamedTuple):
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    eps: float
    theta: float


def dims(side: Dict[str, Any]) -> Dims:
    """Dims from one side (``draft`` / ``target``) of a configuration."""
    h = side["num_attention_heads"]
    return Dims(layers=side["num_hidden_layers"], d=side["hidden_size"],
                heads=h, kv_heads=side["num_key_value_heads"],
                head_dim=side.get("head_dim", side["hidden_size"] // h),
                ff=side["intermediate_size"], vocab=side["vocab_size"],
                eps=float(side["rms_norm_eps"]),
                theta=float(side["rope_theta"]))


def key_of(seed: int, stream: int) -> jax.Array:
    """A PRNG key from any whole-number seed (above 2**32 too)."""
    s = np.random.SeedSequence([seed, stream]).generate_state(1)[0]
    return jax.random.PRNGKey(int(s) & 0x7FFFFFFF)


def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std
            ).astype(jnp.bfloat16)


def _layer(key, m: Dims):
    D, H, KV, hd, F = m.d, m.heads, m.kv_heads, m.head_dim, m.ff
    k = jax.random.split(key, 9)
    return {
        "mixer": {"ln": _normal(k[0], (D,), 0.05),
                  "wq": _normal(k[1], (D, H * hd), 1 / math.sqrt(D)),
                  "wk": _normal(k[2], (D, KV * hd), 1 / math.sqrt(D)),
                  "wv": _normal(k[3], (D, KV * hd), 1 / math.sqrt(D)),
                  "wo": _normal(k[4], (H * hd, D), 1 / math.sqrt(H * hd))},
        "ffn": {"ln": _normal(k[5], (D,), 0.05),
                "wg": _normal(k[6], (D, F), 1 / math.sqrt(D)),
                "wu": _normal(k[7], (D, F), 1 / math.sqrt(D)),
                "wd": _normal(k[8], (F, D), 1 / math.sqrt(F))},
    }


@functools.partial(jax.jit, static_argnums=1)
def make_params(key: jax.Array, m: Dims) -> Dict[str, Any]:
    """Every weight of one model; layers are made one at a time inside
    the call (``lax.map``), so no temporary the size of a stack exists."""
    k_lay, k_emb, k_head, k_norm = jax.random.split(key, 4)
    blocks = jax.lax.map(lambda k: _layer(k, m),
                         jax.random.split(k_lay, m.layers))
    return {
        "embed": _normal(k_emb, (m.vocab, m.d), 1 / math.sqrt(m.d)),
        "final_norm": _normal(k_norm, (m.d,), 0.05),
        "blocks": [blocks],
        "rem": [],
        "lm_head": _normal(k_head, (m.d, m.vocab), 1 / math.sqrt(m.d)),
    }


def param_bytes(m: Dims) -> int:
    """Bytes of one model's bf16 weights."""
    D, H, KV, hd, F = m.d, m.heads, m.kv_heads, m.head_dim, m.ff
    per = 2 * D + D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F
    return 2 * (m.layers * per + 2 * m.vocab * D + D)
