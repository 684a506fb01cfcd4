"""Neural-net primitives shared by all six architecture families.

Pure-functional JAX: parameters are nested dicts of ``jnp.ndarray``.  All
attention flows through one chunked online-softmax implementation (memory
O(B·T·chunk) instead of O(B·T·S)) so that the 32k/500k dry-runs lower to a
program that actually fits on a TPU v5e; the Pallas kernels in
``repro.kernels`` are drop-in replacements for the same math.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig

Params = Dict[str, Any]

NEG_INF = -1e30

# Experiment knob (§Perf hillclimb A3): when set to a PartitionSpec for the
# (B, T, KV, G, hd) query tensor, `attend` constrains q so the q-k
# contraction stays hd-sharded (the logits get psummed) instead of SPMD
# all-gathering the hd-sharded KV cache.  Set by launch/steps at trace time.
ATTN_Q_SPEC = None


# ---------------------------------------------------------------------------
# basics
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(dt)


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def rope_sin_cos(positions: jax.Array, head_dim: int, theta: float
                 ) -> Tuple[jax.Array, jax.Array]:
    """positions: (..., T) int -> sin/cos of shape (..., T, head_dim//2)."""
    half = head_dim // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freq
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x: (B, T, H, hd); sin/cos: (B, T, hd//2)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    s, c = sin[..., None, :], cos[..., None, :]  # broadcast over heads
    out = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return out.astype(dt)


def silu(x: jax.Array) -> jax.Array:
    return x * jax.nn.sigmoid(x)


# ---------------------------------------------------------------------------
# chunked (flash-style) attention — pure jnp, memory O(T * chunk)
# ---------------------------------------------------------------------------

def attend(q: jax.Array, k: jax.Array, v: jax.Array,
           q_pos: jax.Array, k_pos: jax.Array, *,
           causal: bool = True, window: int = 0,
           cap: Optional[float] = None, kv_chunk: int = 2048,
           q_ctx: Optional[jax.Array] = None) -> jax.Array:
    """Online-softmax attention.

    q:      (B, T, H, hd)
    k, v:   (B, S, KV, hd)           (KV divides H — GQA)
    q_pos:  (B, T) absolute positions of queries
    k_pos:  (B, S) absolute positions of keys; -1 marks invalid slots
    window: if > 0, keys with q_pos - k_pos >= window are masked (local attn)
    q_ctx:  (B, T) optional per-query causal horizon: keys with
            k_pos > q_ctx are masked instead of k_pos > q_pos.  Parallel
            draft positions (DESIGN.md §7.12) sit at future positions
            (RoPE and window anchored there) but may only see the real
            prefix — the same visibility the paged backend gets for free
            from its ``lens`` bound.  None (default) == q_pos, bitwise.
    Returns (B, T, H, hd).
    """
    B, T, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    if q_ctx is None:
        q_ctx = q_pos
    scale = 1.0 / math.sqrt(hd)
    qf = (q.astype(jnp.float32) * scale).reshape(B, T, KV, G, hd)
    if ATTN_Q_SPEC is not None:
        qf = jax.lax.with_sharding_constraint(qf, ATTN_Q_SPEC)

    n_chunks = max(1, math.ceil(S / kv_chunk))
    pad = n_chunks * kv_chunk - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
    kc = k.reshape(B, n_chunks, kv_chunk, KV, hd)
    vc = v.reshape(B, n_chunks, kv_chunk, KV, hd)
    pc = k_pos.reshape(B, n_chunks, kv_chunk)

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, pb = xs  # (B, C, KV, hd), (B, C, KV, hd), (B, C)
        # qf: (B, T, KV, G, hd) x kb: (B, C, KV, hd) -> (B, KV, G, T, C)
        logits = jnp.einsum("btkgh,bckh->bkgtc", qf, kb.astype(jnp.float32))
        logits = softcap(logits, cap)
        mask = pb[:, None, None, None, :] >= 0
        if causal:
            mask &= pb[:, None, None, None, :] <= q_ctx[:, None, None, :, None]
        if window > 0:
            mask &= (q_pos[:, None, None, :, None] - pb[:, None, None, None, :]
                     ) < window
        logits = jnp.where(mask, logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bkgtc,bckh->btkgh", p, vb.astype(jnp.float32))
        acc_new = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, T), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((B, KV, G, T), dtype=jnp.float32)
    a0 = jnp.zeros((B, T, KV, G, hd), dtype=jnp.float32)
    # checkpoint the chunk body: backward re-computes each chunk's (T, C)
    # logit tile instead of saving all of them (which would reconstitute the
    # full O(T*S) attention matrix that flash attention exists to avoid)
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body), (m0, l0, a0),
        (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4),
         pc.transpose(1, 0, 2)))
    l = jnp.maximum(l, 1e-20).transpose(0, 3, 1, 2)[..., None]
    out = (acc / l).reshape(B, T, H, hd)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# attention layer (GQA + RoPE + optional qk-norm / softcap / sliding window)
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig) -> Params:
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    k1, k2, k3, k4 = jax.random.split(key, 4)
    dt = cfg.jdtype
    s = 1.0 / math.sqrt(D)
    p = {
        "ln": jnp.zeros((D,), dt),
        "wq": (jax.random.normal(k1, (D, H * hd)) * s).astype(dt),
        "wk": (jax.random.normal(k2, (D, KV * hd)) * s).astype(dt),
        "wv": (jax.random.normal(k3, (D, KV * hd)) * s).astype(dt),
        "wo": (jax.random.normal(k4, (H * hd, D)) * (1.0 / math.sqrt(H * hd))
               ).astype(dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dt)
        p["k_norm"] = jnp.zeros((hd,), dt)
    return p


def attention(p: Params, x: jax.Array, cfg: ModelConfig, *,
              positions: jax.Array,
              cache: Optional[Params] = None,
              window: int = 0,
              kv_chunk: int = 2048,
              cache_mode: str = "append",
              paged: Optional[Tuple[jax.Array, jax.Array]] = None,
              paged_backend: Optional[str] = None,
              pdraft: Optional[Params] = None
              ) -> Tuple[jax.Array, Optional[Params]]:
    """One attention block (pre-norm, residual outside).

    cache (optional): {"k": (B, Sc, KV, hd), "v": ..., "pos": (B, Sc) int32}
    ``positions`` are the absolute positions of the T tokens in ``x``.
    Cache entries are written at slot ``position % Sc`` (ring buffer — exact
    for local layers with Sc == window; for global layers Sc >= max_len so
    the ring never wraps).

    cache_mode:
      "append" — attend over (pre-write cache ∪ chunk).  For local layers
        this is required for exactness: writing first would evict ring slots
        still inside *earlier* chunk queries' windows.  (Global layers never
        evict, so they use the cheaper post-write path.)
      "fresh"  — single-shot prefill into an empty cache: attend over the
        chunk itself, then write the tail (avoids attending Sc dead slots).

    Paged decode path (DESIGN.md §7.5): a cache holding "k_pages"/"v_pages"
    (model.init_paged_cache) stores KV physically scattered across
    fixed-size pages; ``paged`` must then carry the per-call page-table view
    ``(table (B, n_max) int32, lens (B,) int32)``.  New KV is scattered at
    page ``table[b, pos // ps]`` slot ``pos % ps``; writes at positions >=
    lens (batch padding / idle rows) are routed to the trash page (the last
    physical page) so they can never clobber a live or COW-shared slot.
    Attention runs in-place over the pages via the Pallas paged kernel —
    no gather, no dense copy (causal only: decode never runs bidirectional).

    Parallel draft positions (DESIGN.md §7.12): ``pdraft`` =
    ``{"cols": (B, T) bool, "ctx": (B, T) int32}`` marks chunk columns that
    are draft slots rather than real tokens.  Slot columns keep their true
    positions for RoPE and window anchoring, but (a) their KEYS are stored
    with position -1 so no query — including other slots — can ever see
    them (the paged backend gets the same for free: slot positions sit at
    >= lens and route to the trash page), and (b) their QUERIES are clamped
    to the ``ctx`` causal horizon (the last real position), so every slot's
    hidden state is a function of the committed prefix only.
    """
    B, T, D = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q = (h @ p["wq"]).reshape(B, T, H, hd)
    k = (h @ p["wk"]).reshape(B, T, KV, hd)
    v = (h @ p["wv"]).reshape(B, T, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    sin, cos = rope_sin_cos(positions, hd, cfg.rope_theta)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)

    # parallel draft slots: keys stored at position -1 (invisible to every
    # query), queries clamped to the last real position (docstring)
    store_pos, q_ctx = positions, None
    if pdraft is not None:
        store_pos = jnp.where(pdraft["cols"], -1, positions)
        q_ctx = pdraft["ctx"]

    if cache is not None and "k_pages" in cache:
        from repro.kernels import ops as _ops
        assert paged is not None, \
            "a paged cache needs the (table, lens) view for this call"
        table, lens = paged
        table = table.astype(jnp.int32)
        ps = cache["k_pages"].shape[2]
        trash = cache["k_pages"].shape[0] - 1
        lp = jnp.minimum(positions // ps, table.shape[1] - 1)
        page = jnp.take_along_axis(table, lp, axis=1)           # (B, T)
        page = jnp.where(positions < lens[:, None], page, trash)
        off = positions % ps
        # head-major pages (P, KV, ps, hd): the two advanced indices put
        # (B, T) first, so the update is k's own (B, T, KV, hd) layout
        ck = cache["k_pages"].at[page, :, off].set(
            k.astype(cache["k_pages"].dtype))
        cv = cache["v_pages"].at[page, :, off].set(
            v.astype(cache["v_pages"].dtype))
        out = _ops.paged_attention(q, ck, cv, table, lens, positions[:, 0],
                                   window=window, cap=cfg.attn_softcap,
                                   backend=paged_backend)
        return (out.reshape(B, T, H * hd) @ p["wo"],
                {"k_pages": ck, "v_pages": cv})

    new_cache = None
    if cache is not None:
        Sc = cache["k"].shape[1]
        # ring buffer: when the incoming chunk exceeds the ring, only its
        # tail survives — slice BEFORE the scatter so no slot is written
        # twice (duplicate scatter indices have unspecified write order)
        kw, vw, pw, pv = k, v, positions, store_pos
        if T > Sc:
            kw, vw, pw, pv = (k[:, -Sc:], v[:, -Sc:], positions[:, -Sc:],
                              store_pos[:, -Sc:])
        # slot index from the TRUE position (a draft slot parks where the
        # real token will later land); the stored pos value may be -1
        slots = pw % Sc                                           # (B, Tw)
        bidx = jnp.arange(B)[:, None]
        ck = cache["k"].at[bidx, slots].set(kw)
        cv = cache["v"].at[bidx, slots].set(vw)
        cp = cache["pos"].at[bidx, slots].set(pv)
        new_cache = {"k": ck, "v": cv, "pos": cp}
        if cache_mode == "fresh":
            k_all, v_all, kpos = k, v, store_pos
        elif window > 0:
            # pre-write cache ∪ chunk (see docstring).  Stale cache entries
            # at/after the chunk start (possible after a speculative
            # rollback) would duplicate chunk positions — mask them out.
            old_pos = jnp.where(cache["pos"] >= positions[:, :1], -1,
                                cache["pos"])
            k_all = jnp.concatenate([cache["k"], k], axis=1)
            v_all = jnp.concatenate([cache["v"], v], axis=1)
            kpos = jnp.concatenate([old_pos, store_pos], axis=1)
        else:
            k_all, v_all, kpos = ck, cv, cp
    else:
        k_all, v_all, kpos = k, v, store_pos

    out = attend(q, k_all, v_all, positions, kpos,
                 causal=cfg.causal, window=window, cap=cfg.attn_softcap,
                 kv_chunk=kv_chunk, q_ctx=q_ctx)
    return out.reshape(B, T, H * hd) @ p["wo"], new_cache


def init_attn_cache(cfg: ModelConfig, batch: int, max_len: int, window: int,
                    ring_slack: int = 0) -> Params:
    """ring_slack pads a sliding-window ring beyond ``window`` slots.
    Sequential decode never needs it (writes advance monotonically), but
    batched speculative decode writes pads/drafts up to a round's span
    AHEAD of a row's logical length: with Sc == window such a write evicts
    the key at ``pos - window``, which is still inside the window of the
    row's post-rollback queries.  With Sc >= window + slack (slack >= max
    overshoot + rollback span) every evicted key is provably outside all
    future windows.  Batched bucketed prefill (DESIGN.md §7.8) leans on
    the same guarantee: prompts pad up a fixed-quantum length ladder, and
    the serving engines fold that quantum into the slack so prefill pad
    writes can never wrap live window state either."""
    Sc = min(window + ring_slack, max_len) if window > 0 else max_len
    KV, hd = cfg.num_kv_heads, cfg.hd
    dt = cfg.jdtype
    return {
        "k": jnp.zeros((batch, Sc, KV, hd), dt),
        "v": jnp.zeros((batch, Sc, KV, hd), dt),
        "pos": jnp.full((batch, Sc), -1, jnp.int32),
    }


def init_paged_attn_cache(cfg: ModelConfig, num_pages: int, page_size: int
                          ) -> Params:
    """Physically paged KV storage for one attention slot: page id ->
    head-major (KV, page_size, hd) tiles (the layout the paged kernel's
    blocks need, kernels/paged_attention.py).  One extra trash page
    (index ``num_pages``)
    absorbs masked pad writes.  No batch axis — rows are page-table views,
    validity is positional (pos < lens), so no "pos" leaf either."""
    KV, hd = cfg.num_kv_heads, cfg.hd
    dt = cfg.jdtype
    return {
        "k_pages": jnp.zeros((num_pages + 1, KV, page_size, hd), dt),
        "v_pages": jnp.zeros((num_pages + 1, KV, page_size, hd), dt),
    }


# ---------------------------------------------------------------------------
# dense FFN (SwiGLU)
# ---------------------------------------------------------------------------

def init_ffn(key, cfg: ModelConfig) -> Params:
    D, F = cfg.d_model, cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    dt = cfg.jdtype
    s_in, s_out = 1.0 / math.sqrt(D), 1.0 / math.sqrt(F)
    return {
        "ln": jnp.zeros((D,), dt),
        "wg": (jax.random.normal(k1, (D, F)) * s_in).astype(dt),
        "wu": (jax.random.normal(k2, (D, F)) * s_in).astype(dt),
        "wd": (jax.random.normal(k3, (F, D)) * s_out).astype(dt),
    }


def ffn(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    return (silu(h @ p["wg"]) * (h @ p["wu"])) @ p["wd"]


# ---------------------------------------------------------------------------
# MoE FFN — capacity-based scatter dispatch (GShard-style, gather variant)
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig) -> Params:
    D, F, E = cfg.d_model, cfg.expert_ff, cfg.num_experts
    k0, k1, k2, k3 = jax.random.split(key, 4)
    dt = cfg.jdtype
    s_in, s_out = 1.0 / math.sqrt(D), 1.0 / math.sqrt(F)
    return {
        "ln": jnp.zeros((D,), dt),
        "router": (jax.random.normal(k0, (D, E)) * s_in).astype(jnp.float32),
        "wg": (jax.random.normal(k1, (E, D, F)) * s_in).astype(dt),
        "wu": (jax.random.normal(k2, (E, D, F)) * s_in).astype(dt),
        "wd": (jax.random.normal(k3, (E, F, D)) * s_out).astype(dt),
    }


def moe_capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = math.ceil(n_tokens * cfg.num_experts_per_tok / cfg.num_experts
                  * cfg.capacity_factor)
    return max(4, min(c, n_tokens))


def moe_ffn(p: Params, x: jax.Array, cfg: ModelConfig,
            moe_specs: Optional[dict] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """Top-k routed MoE.  Returns (out, aux_load_balance_loss).

    moe_specs (distributed runs): {"buf": PartitionSpec for the (E, C, D)
    dispatch buffer, "y": spec for the (B, T, D) output}.  The dispatch
    buffer is a scatter target with data-dependent indices, so SPMD cannot
    infer a sharding for it and replicates (54 GiB/dev for jamba train —
    EXPERIMENTS.md §Perf It.7); constraining its D axis onto "model" makes
    the scatter local per D-shard and orients expert TP along D.
    """
    B, T, D = x.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    flat = h.reshape(B * T, D)
    n = B * T
    C = moe_capacity(cfg, n)

    logits = flat.astype(jnp.float32) @ p["router"]            # (n, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, K)                        # (n, K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # position of each (token, choice) within its expert via sort-based
    # ranking — O(nK log nK) and O(nK) memory (the dense one-hot cumsum
    # would materialize an (nK, E) tensor: 21 GiB/device for granite-40e
    # at train_4k, see EXPERIMENTS.md §Perf)
    e_flat = eidx.reshape(n * K)
    order = jnp.argsort(e_flat, stable=True)                    # (n*K,)
    sorted_e = e_flat[order]
    counts = jnp.zeros((E,), jnp.int32).at[e_flat].add(1)
    starts = jnp.cumsum(counts) - counts                        # exclusive
    pos_sorted = jnp.arange(n * K, dtype=jnp.int32) - starts[sorted_e]
    pos = jnp.zeros((n * K,), jnp.int32).at[order].set(pos_sorted)
    keep = (pos < C)

    safe_pos = jnp.where(keep, pos, C - 1)
    buf = jnp.zeros((E, C, D), flat.dtype)
    if moe_specs is not None:
        buf = jax.lax.with_sharding_constraint(buf, moe_specs["buf"])
    src = jnp.repeat(flat, K, axis=0) * keep[:, None].astype(flat.dtype)
    buf = buf.at[e_flat, safe_pos].add(jnp.where(keep[:, None], src, 0))
    if moe_specs is not None:
        buf = jax.lax.with_sharding_constraint(buf, moe_specs["buf"])

    hg = jnp.einsum("ecd,edf->ecf", buf, p["wg"])
    hu = jnp.einsum("ecd,edf->ecf", buf, p["wu"])
    out_buf = jnp.einsum("ecf,efd->ecd", silu(hg) * hu, p["wd"])
    if moe_specs is not None:
        out_buf = jax.lax.with_sharding_constraint(out_buf,
                                                   moe_specs["buf"])

    gathered = out_buf[e_flat, safe_pos]                        # (n*K, D)
    w = (gate.reshape(n * K) * keep).astype(flat.dtype)
    y = (gathered * w[:, None]).reshape(n, K, D).sum(axis=1)

    # load-balance auxiliary loss (Switch-style)
    frac_tokens = counts.astype(jnp.float32) / jnp.maximum(n * K, 1)
    frac_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return y.reshape(B, T, D), aux


# ---------------------------------------------------------------------------
# Mamba-1 block (selective scan)
# ---------------------------------------------------------------------------

def init_mamba(key, cfg: ModelConfig) -> Params:
    D, E, N, R, Cv = (cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dtr,
                      cfg.ssm_conv)
    keys = jax.random.split(key, 6)
    dt = cfg.jdtype
    s = 1.0 / math.sqrt(D)
    a_init = jnp.log(jnp.broadcast_to(
        jnp.arange(1, N + 1, dtype=jnp.float32), (E, N)))
    return {
        "ln": jnp.zeros((D,), dt),
        "in_proj": (jax.random.normal(keys[0], (D, 2 * E)) * s).astype(dt),
        "conv_w": (jax.random.normal(keys[1], (Cv, E)) / math.sqrt(Cv)
                   ).astype(dt),
        "conv_b": jnp.zeros((E,), dt),
        "x_db": (jax.random.normal(keys[2], (E, R + 2 * N))
                 / math.sqrt(E)).astype(dt),
        "dt_w": (jax.random.normal(keys[3], (R, E)) / math.sqrt(R)
                 ).astype(dt),
        "dt_b": jnp.full((E,), -4.6, dt),  # softplus^-1(0.01) ≈ -4.6
        "A_log": a_init,                    # float32 for stability
        "Dskip": jnp.ones((E,), jnp.float32),
        "out_proj": (jax.random.normal(keys[4], (E, D)) / math.sqrt(E)
                     ).astype(dt),
    }


# Experiment knob: ring-mode decode scan implementation — "jnp" (pure-jnp
# per-step scan) or "pallas" (kernels.ssm_scan with return_states).  Module
# level like ATTN_Q_SPEC so the serving tests can flip it without re-plumbing.
SSM_SCAN_IMPL = "jnp"


def init_mamba_cache(cfg: ModelConfig, batch: int, ring: int = 0) -> Params:
    """Recurrent decode state for one mamba slot.

    ring == 0 (sequential decode / training): the carried state only —
    rollback needs checkpoint+replay (runtime/runner.py).

    ring > 0 (batched serving, DESIGN.md §7.6): a position-indexed
    checkpoint ring.  Slot ``k % ring`` holds the post-step state (SSM
    carry h + causal-conv tail) after the row's k-th token; slot 0 is the
    zero state so a fresh row is readable at position 0.  A forward
    starting at position p0 *loads* its state from slot ``p0 % ring``,
    which makes SSM rollback purely positional — shrink the logical length
    and the next forward resumes from the accept-point checkpoint, O(1)
    per row, no replay — exactly symmetric to the attention cache's
    causally-masked stale slots."""
    E, N, Cv = cfg.d_inner, cfg.ssm_state, cfg.ssm_conv
    if ring > 0:
        return {
            "h_ring": jnp.zeros((batch, ring, E, N), jnp.float32),
            "conv_ring": jnp.zeros((batch, ring, Cv - 1, E), cfg.jdtype),
        }
    return {
        "conv": jnp.zeros((batch, Cv - 1, E), cfg.jdtype),
        "ssm": jnp.zeros((batch, E, N), jnp.float32),
    }


def _causal_conv(xp: jax.Array, w: jax.Array, b: jax.Array,
                 prev: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv1d.  xp: (B, T, E); w: (Cv, E); prev: (B,Cv-1,E)."""
    Cv = w.shape[0]
    if prev is None:
        prev = jnp.zeros((xp.shape[0], Cv - 1, xp.shape[2]), xp.dtype)
    full = jnp.concatenate([prev.astype(xp.dtype), xp], axis=1)   # (B,T+Cv-1,E)
    out = sum(full[:, i:i + xp.shape[1]] * w[i] for i in range(Cv)) + b
    new_prev = full[:, full.shape[1] - (Cv - 1):]
    return out, new_prev


def mamba(p: Params, x: jax.Array, cfg: ModelConfig, *,
          cache: Optional[Params] = None,
          positions: Optional[jax.Array] = None,
          scan_impl: Optional[str] = None
          ) -> Tuple[jax.Array, Optional[Params]]:
    """Mamba-1 mixer.  x: (B, T, D) -> (B, T, D).

    A ring cache (init_mamba_cache with ring > 0) additionally needs
    ``positions`` (B, T): the initial state is loaded from the checkpoint
    slot of each row's start position (position 0 = zero state) and a
    post-step checkpoint is written for every emitted position — the
    serving layer's rollback/snapshot substrate (DESIGN.md §7.6)."""
    B, T, D = x.shape
    E, N, R = cfg.d_inner, cfg.ssm_state, cfg.dtr
    Cv = cfg.ssm_conv
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    xz = h @ p["in_proj"]
    xp, z = jnp.split(xz, 2, axis=-1)                            # (B,T,E) each

    ring = cache is not None and "h_ring" in cache
    if ring:
        assert positions is not None, "ring SSM cache needs positions"
        Rg = cache["h_ring"].shape[1]
        p0 = positions[:, 0].astype(jnp.int32)                   # (B,)
        bidx = jnp.arange(B)
        slot0 = p0 % Rg
        fresh = (p0 == 0)                    # new row: zero state, not slot 0
        h0 = jnp.where(fresh[:, None, None], 0.0,
                       cache["h_ring"][bidx, slot0])
        prev = jnp.where(fresh[:, None, None],
                         jnp.zeros((), cache["conv_ring"].dtype),
                         cache["conv_ring"][bidx, slot0])
    else:
        prev = cache["conv"] if cache is not None else None
        h0 = (cache["ssm"] if cache is not None
              else jnp.zeros((B, E, N), jnp.float32))
    xc, new_conv = _causal_conv(xp, p["conv_w"], p["conv_b"], prev)
    xc = silu(xc)

    dbc = xc @ p["x_db"]
    dt_raw = dbc[..., :R]
    Bmat = dbc[..., R:R + N].astype(jnp.float32)                  # (B,T,N)
    Cmat = dbc[..., R + N:].astype(jnp.float32)
    delta = jax.nn.softplus(dt_raw @ p["dt_w"] + p["dt_b"]
                            ).astype(jnp.float32)                 # (B,T,E)
    A = -jnp.exp(p["A_log"])                                      # (E,N)
    xf = xc.astype(jnp.float32)

    def step(hprev, xs):
        d_t, x_t, b_t, c_t = xs            # (B,E), (B,E), (B,N), (B,N)
        decay_t = jnp.exp(d_t[..., None] * A)
        h_t = decay_t * hprev + (d_t * x_t)[..., None] * b_t[:, None, :]
        y_t = jnp.einsum("ben,bn->be", h_t, c_t)
        return h_t, y_t

    hs = None
    if ring:
        # decode path: keep every post-step carry — the (B, T, E, N)
        # checkpoint tensor IS the product here, T is a draft span, not a
        # training sequence, so materializing it is the point, not a leak.
        impl = scan_impl or SSM_SCAN_IMPL
        if impl == "pallas":
            from repro.kernels import ops as _ops
            y, hT, hs = _ops.ssm_scan(xc, delta, Bmat, Cmat, A, p["Dskip"],
                                      h0, return_states=True)
        else:
            def step_full(hprev, xs):
                h_t, y_t = step(hprev, xs)
                return h_t, (y_t, h_t)
            hT, (ys, hs) = jax.lax.scan(
                step_full, h0,
                (delta.transpose(1, 0, 2), xf.transpose(1, 0, 2),
                 Bmat.transpose(1, 0, 2), Cmat.transpose(1, 0, 2)))
            y = ys.transpose(1, 0, 2) + p["Dskip"] * xf            # (B,T,E)
            hs = hs.transpose(1, 0, 2, 3)                          # (B,T,E,N)
    else:
        # the (B,T,E,N) decay/drive tensors are NEVER materialized: each
        # scan step builds its own (B,E,N) slice from delta_t / B_t / C_t —
        # this is the memory shape the Pallas ssm_scan kernel implements on
        # TPU.  Two-level scan: the outer chunk scan saves only h at chunk
        # boundaries for the backward pass (checkpointed body); per-step
        # carries exist only transiently within one chunk —
        # O(T/chunk + chunk) memory, not O(T).
        chunk = min(128, T)
        pad = (-T) % chunk
        nchunks = (T + pad) // chunk

        def padt(a):
            return jnp.pad(a, ((0, 0), (0, pad), (0, 0))) if pad else a

        def to_chunks(a):  # (B, T, X) -> (nchunks, chunk, B, X)
            return padt(a).reshape(B, nchunks, chunk, -1).transpose(1, 2, 0, 3)

        seq = (to_chunks(delta), to_chunks(xf), to_chunks(Bmat),
               to_chunks(Cmat))

        @jax.checkpoint
        def chunk_body(h, xs):
            return jax.lax.scan(step, h, xs)

        hT, ys = jax.lax.scan(chunk_body, h0, seq)
        y = ys.reshape(nchunks * chunk, B, E).transpose(1, 0, 2)[:, :T]
        y = y + p["Dskip"] * xf                                    # (B,T,E)
    y = y.astype(x.dtype) * silu(z)
    out = y @ p["out_proj"]

    new_cache = None
    if ring:
        # write one checkpoint per emitted position: the state after the
        # row's k-th token lands in slot k % Rg.  Pad steps of a batched
        # call write *future* slots (length > the row's logical length) and
        # are overwritten by real writes before any load can see them —
        # the same masked-until-overwritten discipline as pad KV writes.
        # Only the trailing min(T, Rg) steps are scattered: a longer span
        # (prefill) laps the ring and the survivors are exactly the last
        # Rg checkpoints — slicing first keeps every scatter index unique
        # (duplicate scatter writes have unspecified order).
        Tr = min(T, Rg)
        t_idx = jnp.arange(T - Tr, T, dtype=jnp.int32)             # (Tr,)
        slots = (p0[:, None] + t_idx[None] + 1) % Rg               # (B, Tr)
        h_ring = cache["h_ring"].at[bidx[:, None], slots].set(hs[:, T - Tr:])
        full = jnp.concatenate([prev.astype(xp.dtype), xp], axis=1)
        widx = t_idx[:, None] + 1 + jnp.arange(Cv - 1)[None]       # (Tr,Cv-1)
        tails = full[:, widx]                                  # (B,Tr,Cv-1,E)
        conv_ring = cache["conv_ring"].at[bidx[:, None], slots].set(
            tails.astype(cache["conv_ring"].dtype))
        new_cache = {"h_ring": h_ring, "conv_ring": conv_ring}
    elif cache is not None:
        new_cache = {"conv": new_conv.astype(cache["conv"].dtype), "ssm": hT}
    return out, new_cache
