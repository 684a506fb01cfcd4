"""Public jit'd wrappers for the Pallas kernels.

``interpret`` defaults from the platform alone: compiled Mosaic kernels on
TPU, the Pallas interpreter everywhere else (CPU tests).  Callers may pass
``interpret`` explicitly; no environment variable changes the default.
"""
from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import paged as _paged
from repro.kernels import paged_attention as _pa
from repro.kernels import ssm_scan as _ssm
from repro.kernels import verify_accept as _va


def default_interpret() -> bool:
    """Pallas interpret mode off-TPU, compiled Mosaic kernels on TPU."""
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, q_pos, k_pos, *, causal=True, window=0,
                    cap: Optional[float] = None, bq=128, bk=128,
                    interpret: Optional[bool] = None):
    """Prefill/decode attention.  See kernels.flash_attention."""
    it = default_interpret() if interpret is None else interpret
    return _fa.flash_attention(q, k, v, q_pos, k_pos, causal=causal,
                               window=window, cap=cap, bq=bq, bk=bk,
                               interpret=it)


def branch_decode_attention(q, prefix_k, prefix_v, prefix_pos,
                            suffix_k, suffix_v, suffix_pos, q_pos, *,
                            cap: Optional[float] = None,
                            interpret: Optional[bool] = None):
    """Shared-prefix branch decode (Eq. 8).

    q: (k, Tq, H, hd) — one row per branch; prefix_k/v: (1, Sp, KV, hd)
    stored ONCE; suffix_k/v: (k, Ss, KV, hd) per-branch diverging KV.
    Two flash passes (prefix broadcast via index_map, suffix per-branch)
    merged with the standard (m, l) combination.
    """
    it = default_interpret() if interpret is None else interpret
    o1, m1, l1 = _fa.flash_attention(
        q, prefix_k, prefix_v, q_pos, prefix_pos, causal=True, cap=cap,
        out_stats=True, shared_kv=True, interpret=it)
    o2, m2, l2 = _fa.flash_attention(
        q, suffix_k, suffix_v, q_pos, suffix_pos, causal=True, cap=cap,
        out_stats=True, interpret=it)
    m = jnp.maximum(m1, m2)
    w1 = l1 * jnp.exp(m1 - m)
    w2 = l2 * jnp.exp(m2 - m)
    denom = jnp.maximum(w1 + w2, 1e-20)
    kb, Tq, H, hd = q.shape

    def expand(w):  # (B, KV, G, T) -> (B, T, H, 1)
        return w.transpose(0, 3, 1, 2).reshape(kb, Tq, H)[..., None]

    out = (o1.astype(jnp.float32) * expand(w1 / denom)
           + o2.astype(jnp.float32) * expand(w2 / denom))
    return out.astype(q.dtype)


def ssm_scan(x, dt, Bm, Cm, A, D, h0, *, bT=128, bE=256,
             return_states: bool = False,
             interpret: Optional[bool] = None) -> Tuple[jax.Array, ...]:
    """Selective scan; ``return_states`` adds the per-step carries hs
    (B, T, E, N) — the SSM rollback checkpoints.  See kernels.ssm_scan."""
    it = default_interpret() if interpret is None else interpret
    return _ssm.ssm_scan(x, dt, Bm, Cm, A, D, h0, bT=bT, bE=bE,
                         return_states=return_states, interpret=it)


def verify_accept(p_logits, q_logits, tokens, uniforms, res_uniforms, *,
                  interpret: Optional[bool] = None):
    it = default_interpret() if interpret is None else interpret
    return _va.verify_accept(p_logits, q_logits, tokens, uniforms,
                             res_uniforms, interpret=it)


def verify_accept_batched(p_logits, q_logits, tokens, lens, uniforms,
                          res_uniforms, *, backend: Optional[str] = None,
                          interpret: Optional[bool] = None):
    """Batched ragged verification (see kernels.verify_accept).

    backend: "pallas" | "xla" | None.  None routes to the pallas kernel on
    TPU and to the compiled XLA path everywhere else (REPRO_VERIFY_BACKEND
    overrides).  The serving engines call the kernel through
    serving/device_loop (kernel_route); their off-TPU verify math lives in
    ``sampling.verify_chain_device``.
    """
    if backend is None:
        backend = os.environ.get("REPRO_VERIFY_BACKEND") or (
            "pallas" if jax.default_backend() == "tpu" else "xla")
    if backend == "xla":
        return _va.verify_accept_batched_xla(p_logits, q_logits, tokens,
                                             lens, uniforms, res_uniforms)
    it = default_interpret() if interpret is None else interpret
    return _va.verify_accept_batched(p_logits, q_logits, tokens, lens,
                                     uniforms, res_uniforms, interpret=it)


def paged_gather(pages, table, valid_len=None, *,
                 interpret: Optional[bool] = None):
    """Gather logical pages through a page table.  See kernels.paged."""
    it = default_interpret() if interpret is None else interpret
    return _paged.paged_gather(jnp.asarray(pages), jnp.asarray(table),
                               valid_len, interpret=it)


def paged_attention(q, k_pages, v_pages, table, lens, q_start, *,
                    window: int = 0, cap: Optional[float] = None,
                    backend: Optional[str] = None,
                    interpret: Optional[bool] = None):
    """Decode attention straight over paged KV through a page table.
    See kernels.paged_attention.

    backend: "pallas" | "xla" | None.  None routes to the Pallas kernel
    (interpret off-TPU) — the single-device fast path.  "xla" selects the
    gather-based twin, which is plain HLO and therefore SPMD-partitionable:
    the mesh serving path (DESIGN.md §7.10) uses it so the KV-head-sharded
    page buffers stay collective-free per shard.  REPRO_PAGED_BACKEND
    overrides a None backend.
    """
    if backend is None:
        backend = os.environ.get("REPRO_PAGED_BACKEND") or "pallas"
    if backend == "xla":
        return _pa.paged_decode_attention_xla(
            q, k_pages, v_pages, jnp.asarray(table), jnp.asarray(lens),
            jnp.asarray(q_start), window=window, cap=cap)
    it = default_interpret() if interpret is None else interpret
    return _pa.paged_decode_attention(
        q, k_pages, v_pages, jnp.asarray(table), jnp.asarray(lens),
        jnp.asarray(q_start), window=window, cap=cap, interpret=it)
