"""Full model: embedding -> scanned periodic layer stack -> logits.

The layer stack repeats ``cfg.pattern`` (a tuple of (mixer, ffn) slots).
The periodic part is executed with ``jax.lax.scan`` over ``n_periods`` with
parameters stacked on a leading axis (one stack per slot), which keeps the
lowered HLO size O(period) instead of O(num_layers) — essential for compiling
72-layer models on a 512-device simulated mesh.  Remainder layers (when
``num_layers % period != 0``) are unrolled.

Forward returns ``(logits, new_cache, aux)`` where ``aux["features"]`` holds
the last-position hidden state after every period/remainder layer — the raw
material for H-RAD's last-K-layer feature vector (Eq. 4 of the paper).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import layers as L
from .config import ModelConfig

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_slot(key, cfg: ModelConfig, slot) -> Params:
    mixer, ffn_kind = slot
    k1, k2 = jax.random.split(key)
    p: Params = {}
    if mixer in ("attn", "local"):
        p["mixer"] = L.init_attention(k1, cfg)
    else:
        p["mixer"] = L.init_mamba(k1, cfg)
    if ffn_kind == "dense":
        p["ffn"] = L.init_ffn(k2, cfg)
    elif ffn_kind == "moe":
        p["ffn"] = L.init_moe(k2, cfg)
    return p


def init_params(key, cfg: ModelConfig) -> Params:
    """Random weights from ``key``.  Each slot's layer stack is built by
    one ``vmap`` over its layer keys, so under ``jit`` the stack is made
    in place — no per-layer copies held beside the stacked result."""
    keys = jax.random.split(key, cfg.num_layers + 3)
    P, nper, nrem = cfg.period, cfg.n_periods, cfg.n_rem
    # periodic stacks: one stacked pytree per slot; slot s of period i
    # draws from keys[i * P + s]
    blocks = [jax.vmap(lambda k, slot=cfg.pattern[s]:
                       _init_slot(k, cfg, slot))(keys[s:nper * P:P])
              for s in range(P)]
    rem = [_init_slot(keys[nper * P + r], cfg, cfg.pattern[r])
           for r in range(nrem)]
    dt = cfg.jdtype
    params: Params = {
        "embed": (jax.random.normal(keys[-1], (cfg.vocab_size, cfg.d_model))
                  * (1.0 / math.sqrt(cfg.d_model))).astype(dt),
        "final_norm": jnp.zeros((cfg.d_model,), dt),
        "blocks": blocks,
        "rem": rem,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(keys[-2], (cfg.d_model, cfg.vocab_size))
            * (1.0 / math.sqrt(cfg.d_model))).astype(dt)
    return params


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------

def _slot_window(cfg: ModelConfig, mixer: str) -> int:
    return cfg.sliding_window if mixer == "local" else 0


def _init_slot_cache(cfg: ModelConfig, slot, batch: int, max_len: int,
                     ssm_ring: int = 0) -> Params:
    mixer, _ = slot
    if mixer in ("attn", "local"):
        # the speculation ring depth doubles as sliding-window slack: both
        # bound how far ahead of a row's logical length writes may land
        return L.init_attn_cache(cfg, batch, max_len,
                                 _slot_window(cfg, mixer),
                                 ring_slack=ssm_ring)
    return L.init_mamba_cache(cfg, batch, ring=ssm_ring)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               ssm_ring: int = 0) -> Params:
    """Decode cache pytree mirroring the params layout.

    Every leaf has a leading "stack" axis (n_periods for the scanned blocks,
    1 for remainder layers) so batch is uniformly axis 1 — branch fork/select
    in the runner rely on this.

    ssm_ring > 0 swaps every mamba slot's carried state for a
    position-indexed checkpoint ring of that depth (layers.init_mamba_cache)
    — required by the batched serving path, whose per-row rollback is
    positional (DESIGN.md §7.6).  0 keeps the sequential checkpoint+replay
    rollback model (runtime/runner.py).
    """
    P, nper, nrem = cfg.period, cfg.n_periods, cfg.n_rem
    blocks = []
    for s in range(P):
        one = _init_slot_cache(cfg, cfg.pattern[s], batch, max_len, ssm_ring)
        blocks.append(jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (nper,) + a.shape).copy()
            if nper > 1 else a[None], one))
    rem = [jax.tree.map(lambda a: a[None],
                        _init_slot_cache(cfg, cfg.pattern[r], batch, max_len,
                                         ssm_ring))
           for r in range(nrem)]
    return {"blocks": blocks, "rem": rem}


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                     *, n_rows: int = 0, ssm_ring: int = 0) -> Params:
    """Physically paged decode cache (DESIGN.md §7.5, §7.8): every attention
    slot stores KV scattered across ``num_pages`` fixed-size pages (+ one
    trash page) addressed per call through a kv_pool page table.

    SSM/hybrid configs build a **mixed pytree**: recurrent state is not
    positional KV and cannot be paged, so every mamba slot instead carries
    the per-row position-indexed checkpoint ring of DESIGN.md §7.6
    (``n_rows`` rows, depth ``ssm_ring``) alongside the paged attention
    slots.  Per-row rollback is positional for both halves — paged slots
    reclaim pages, ring slots resume from the accept-point checkpoint — so
    one forward serves the whole tree.

    Paged leaves keep the same leading stack axis as ``init_cache`` so the
    scan over periods carries them identically — but they have no batch
    axis: batch rows exist only as page-table views passed alongside the
    forward.  Ring leaves keep the batch axis (axis 1 after the stack),
    sized ``n_rows``.
    """
    for mixer, _ in cfg.pattern:
        if mixer == "mamba" and (n_rows <= 0 or ssm_ring <= 0):
            raise ValueError(
                "mamba slots in a paged cache ride per-row checkpoint "
                "rings: pass n_rows > 0 and ssm_ring > 0 (DESIGN.md §7.8)")

    def slot_cache(slot):
        mixer, _ = slot
        if mixer in ("attn", "local"):
            return L.init_paged_attn_cache(cfg, num_pages, page_size)
        return L.init_mamba_cache(cfg, n_rows, ring=ssm_ring)

    P, nper, nrem = cfg.period, cfg.n_periods, cfg.n_rem
    blocks = []
    for s in range(P):
        one = slot_cache(cfg.pattern[s])
        blocks.append(jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (nper,) + a.shape).copy()
            if nper > 1 else a[None], one))
    rem = [jax.tree.map(lambda a: a[None], slot_cache(cfg.pattern[r]))
           for r in range(nrem)]
    return {"blocks": blocks, "rem": rem}


def map_slot_caches(cache: Params, fn) -> Params:
    """Apply ``fn`` to every slot cache dict (blocks + remainder),
    preserving the layout.  The serving DecodeState components use this
    walk to address their own slots inside a mixed pytree (paged attention
    pages next to per-row SSM rings) without if/else chains over leaves."""
    return {"blocks": [fn(c) for c in cache["blocks"]],
            "rem": [fn(c) for c in cache["rem"]]}


def cache_bytes(cfg: ModelConfig, batch: int, max_len: int) -> int:
    cache = jax.eval_shape(lambda: init_cache(cfg, batch, max_len))
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _apply_slot(p: Params, x: jax.Array, cfg: ModelConfig, slot, *,
                positions: jax.Array, cache: Optional[Params],
                kv_chunk: int, moe_specs=None, cache_mode: str = "append",
                paged=None, paged_backend=None, pdraft=None
                ) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    mixer, ffn_kind = slot
    aux_loss = jnp.zeros((), jnp.float32)
    if mixer in ("attn", "local"):
        mx, new_cache = L.attention(
            p["mixer"], x, cfg, positions=positions, cache=cache,
            window=_slot_window(cfg, mixer), kv_chunk=kv_chunk,
            cache_mode=cache_mode, paged=paged,
            paged_backend=paged_backend, pdraft=pdraft)
    else:
        if pdraft is not None:
            raise ValueError(
                "parallel draft positions need attention-only models: a "
                "mamba slot's scan would thread recurrent state through "
                "the draft slots (DESIGN.md §7.12)")
        mx, new_cache = L.mamba(p["mixer"], x, cfg, cache=cache,
                                positions=positions)
    x = x + mx
    if ffn_kind == "dense":
        x = x + L.ffn(p["ffn"], x, cfg)
    elif ffn_kind == "moe":
        y, aux_loss = L.moe_ffn(p["ffn"], x, cfg, moe_specs=moe_specs)
        x = x + y
    return x, new_cache, aux_loss


def forward(params: Params, cfg: ModelConfig, tokens: Optional[jax.Array], *,
            embeds: Optional[jax.Array] = None,
            cache: Optional[Params] = None,
            positions: Optional[jax.Array] = None,
            kv_chunk: int = 2048,
            feature_mode: str = "last",
            logits_mode: str = "all",
            remat: bool = False,
            act_spec=None,
            logits_spec=None,
            moe_specs=None,
            cache_mode: str = "append",
            onehot_embed: bool = False,
            paged=None,
            paged_backend: Optional[str] = None,
            pdraft: Optional[Params] = None
            ) -> Tuple[jax.Array, Optional[Params], Dict[str, jax.Array]]:
    """Run the model.

    tokens:  (B, T) int32 token ids, or None (pure-embedding input).
    embeds:  (B, Tp, d_model) stub frontend embeddings (audio frames / vision
             patches), prepended to the token embeddings when both given.
    cache:   decode cache from ``init_cache`` (or None for cache-less runs);
             a cache from ``init_paged_cache`` additionally needs ``paged``.
    paged:   (table (B, n_max) int32, lens (B,) int32) page-table view for
             a physically paged cache — see layers.attention.
    paged_backend: paged-attention backend override ("xla" for the
             SPMD-partitionable twin under a serving mesh; None = Pallas).
    positions: (B, T_total) absolute positions; default arange.

    feature_mode: "last" -> aux["features"] is (n_points, B, d_model) (hidden
    state at the final position after each period/remainder layer); "all" ->
    (n_points, B, T, d_model) (every position — used by H-RAD's posterior
    drafting on short verification chunks, Sec. 5.2).

    pdraft (DESIGN.md §7.12) marks parallel-draft slot columns:
    ``{"cols": (B, T) bool, "ctx": (B, T) int32, "sidx": (B, T) int32,
    "embed": (K, d_model)}``.  Slot columns replace their token embedding
    with the learned slot embedding ``embed[sidx]``, their keys are stored
    invisible, and their queries are clamped to the ``ctx`` horizon
    (layers.attention); head logits over the slot hidden states come from
    ``draft_head_logits`` on aux["features"][-1].  Attention-only models
    (a mamba slot raises).

    Returns (logits (B, T_total, vocab), new_cache, aux).
    """
    parts = []
    if embeds is not None:
        parts.append(embeds.astype(cfg.jdtype))
    if tokens is not None:
        if onehot_embed:
            # distributed embedding lookup as a one-hot matmul: contracts the
            # vocab-sharded axis cleanly (a plain gather over a model-sharded
            # table makes SPMD all-gather + replicate — see EXPERIMENTS §Perf)
            oh = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=cfg.jdtype)
            emb = oh @ params["embed"] * math.sqrt(cfg.d_model)
        else:
            emb = params["embed"][tokens] * math.sqrt(cfg.d_model)
        if pdraft is not None:
            K = pdraft["embed"].shape[0]
            se = (pdraft["embed"][jnp.clip(pdraft["sidx"], 0, K - 1)]
                  * math.sqrt(cfg.d_model))
            emb = jnp.where(pdraft["cols"][..., None],
                            se.astype(emb.dtype), emb)
        parts.append(emb.astype(cfg.jdtype))
    x = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
    if act_spec is not None:
        x = jax.lax.with_sharding_constraint(x, act_spec)
    B, T, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    # attention only needs the column mask + causal horizon
    pd_attn = None
    if pdraft is not None:
        pd_attn = {"cols": jnp.broadcast_to(pdraft["cols"], (B, T)),
                   "ctx": jnp.broadcast_to(pdraft["ctx"], (B, T))}

    P, nper = cfg.period, cfg.n_periods
    blocks_cache = cache["blocks"] if cache is not None else [None] * P

    def period_body(carry, xs):
        x = carry
        slot_params, slot_caches = xs
        new_caches, feats, aux = [], None, jnp.zeros((), jnp.float32)
        for s in range(P):
            x, nc, al = _apply_slot(
                slot_params[s], x, cfg, cfg.pattern[s],
                positions=positions, cache=slot_caches[s],
                kv_chunk=kv_chunk, moe_specs=moe_specs,
                cache_mode=cache_mode, paged=paged,
                paged_backend=paged_backend, pdraft=pd_attn)
            new_caches.append(nc)
            aux = aux + al
        feat = x[:, -1, :] if feature_mode == "last" else x
        return x, (tuple(new_caches), feat, aux)

    if nper > 0:
        xs = (tuple(params["blocks"]), tuple(blocks_cache))
        body = (jax.checkpoint(period_body,
                               policy=jax.checkpoint_policies.nothing_saveable)
                if remat else period_body)
        x, (new_block_caches, per_feats, per_aux) = jax.lax.scan(
            body, x, xs)
        feats = [per_feats[i] for i in range(nper)]
        moe_aux = per_aux.sum()
        new_blocks = list(new_block_caches)
    else:
        feats, moe_aux, new_blocks = [], jnp.zeros((), jnp.float32), []

    # remainder layers (unrolled)
    rem_cache = cache["rem"] if cache is not None else [None] * cfg.n_rem
    new_rem = []
    for r in range(cfg.n_rem):
        rc = (jax.tree.map(lambda a: a[0], rem_cache[r])
              if rem_cache[r] is not None else None)
        slot_r = cfg.pattern[r]

        def apply_r(p_, x_, pos_, _slot=slot_r, _rc=rc):
            return _apply_slot(p_, x_, cfg, _slot, positions=pos_,
                               cache=_rc, kv_chunk=kv_chunk,
                               moe_specs=moe_specs, cache_mode=cache_mode,
                               paged=paged, paged_backend=paged_backend,
                               pdraft=pd_attn)

        if remat:
            apply_r = jax.checkpoint(
                apply_r, policy=jax.checkpoint_policies.nothing_saveable)
        x, nc, al = apply_r(params["rem"][r], x, positions)
        if nc is not None:
            nc = jax.tree.map(lambda a: a[None], nc)
        new_rem.append(nc)
        moe_aux = moe_aux + al
        feats.append(x[:, -1, :] if feature_mode == "last" else x)

    if logits_mode == "last":
        x = x[:, -1:]          # prefill: only the final position's logits
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = (x @ head).astype(jnp.float32)
    if logits_spec is not None:
        logits = jax.lax.with_sharding_constraint(logits, logits_spec)
    logits = L.softcap(logits, cfg.final_softcap)

    new_cache = None
    if cache is not None:
        new_cache = {"blocks": new_blocks, "rem": new_rem}
    empty = ((0, B, cfg.d_model) if feature_mode == "last"
             else (0, B, T, cfg.d_model))
    aux = {"features": jnp.stack(feats) if feats else
           jnp.zeros(empty, cfg.jdtype),
           "moe_aux": moe_aux}
    return logits, new_cache, aux


# ---------------------------------------------------------------------------
# multi-token draft head (single-pass parallel drafting, DESIGN.md §7.12)
# ---------------------------------------------------------------------------

def init_draft_heads(key, cfg: ModelConfig, K: int) -> Params:
    """K parallel-position draft heads + K learned slot embeddings.

    Slot j (1-indexed) rides at position ``last_real + j`` of a draft
    forward with its token embedding replaced by ``mask_embed[j-1]``; head
    j maps the slot's final-layer hidden state to the distribution of the
    token at ``last_real + j + 1`` given the committed prefix only.
    """
    k1, k2 = jax.random.split(key)
    dt = cfg.jdtype
    s = 1.0 / math.sqrt(cfg.d_model)
    return {
        "mask_embed": (jax.random.normal(k1, (K, cfg.d_model)) * s
                       ).astype(dt),
        "heads": (jax.random.normal(k2, (K, cfg.d_model, cfg.vocab_size))
                  * s).astype(dt),
    }


def draft_head_logits(params: Params, cfg: ModelConfig, dhead: Params,
                      hidden: jax.Array, j0: int = 0) -> jax.Array:
    """Head logits over slot hidden states.

    hidden: (..., n, d_model) final-layer (pre-final-norm) hidden states at
    slot positions j0+1 .. j0+n (aux["features"][-1] columns).  Applies the
    model's own final norm + softcap so head logits live on the same scale
    as the AR logits they are concatenated with.  Returns (..., n, vocab)
    float32.
    """
    n = hidden.shape[-2]
    hn = L.rms_norm(hidden, params["final_norm"], cfg.norm_eps)
    lg = jnp.einsum("...nd,ndv->...nv", hn.astype(jnp.float32),
                    dhead["heads"][j0:j0 + n].astype(jnp.float32))
    return L.softcap(lg, cfg.final_softcap)


def prefill(params, cfg, tokens, *, cache, embeds=None, kv_chunk: int = 2048):
    """Prefill: forward over the prompt writing the cache."""
    return forward(params, cfg, tokens, embeds=embeds, cache=cache,
                   kv_chunk=kv_chunk)


def decode_step(params, cfg, tokens, *, cache, pos, kv_chunk: int = 2048):
    """Decode T new tokens (T = 1 for plain AR, T = gamma for verification).

    pos: (B,) int32 — the absolute position of the *first* new token.
    """
    B, T = tokens.shape
    positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    return forward(params, cfg, tokens, cache=cache, positions=positions,
                   kv_chunk=kv_chunk)
