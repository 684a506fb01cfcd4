"""Trained draft/target pairs on the Zipf-Markov language, cached to disk.

Two pairs mirror the paper's regimes:

  * "misaligned" — tiny 1-layer draft vs 4-layer target (the paper's
    68M-vs-13B regime, alpha ~ 0.4-0.6, rollback-dominated)
  * "aligned"    — 2-layer d96 draft vs 4-layer target (the paper's
    Deepseek/LLaMA-3.1 regime, alpha ~ 0.75+, parallelism-dominated)

``get_pair`` trains on first use (~1-2 min CPU) and caches under
``.cache/pairs``.
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import sys
from typing import Tuple

import jax

from repro.configs.paper_pairs import PAPER_PAIRS
from repro.data.synthetic import ZipfMarkov
from repro.models import model as M
from repro.models.config import ModelConfig, dense_pattern
from repro.training import checkpoint as ckpt
from repro.training.train import (TrainConfig, train_draft_heads,
                                  train_lm)
from repro.training.optim import AdamWConfig

CACHE_DIR = os.environ.get("REPRO_PAIR_CACHE", ".cache/pairs")

VOCAB = 199


def _cfg(name: str, layers: int, d: int, heads: int) -> ModelConfig:
    return ModelConfig(
        name=name, family="dense", num_layers=layers, d_model=d,
        num_heads=heads, num_kv_heads=max(1, heads // 2), d_ff=4 * d,
        vocab_size=VOCAB, pattern=dense_pattern(0), dtype="float32")


TARGET_CFG = _cfg("zm-target", 4, 128, 4)
# same 1-layer d32 draft arch; alignment is steered by training budget:
# 200 steps -> ~0.53 argmax agreement with the target (the paper's poorly
# aligned 68M-vs-13B regime); 400 steps -> ~0.91 (Deepseek/LLaMA-3.1 regime)
DRAFT_MIS_CFG = _cfg("zm-draft-mis", 1, 32, 2)
DRAFT_ALI_CFG = _cfg("zm-draft-ali", 1, 32, 2)
MIS_STEPS = 200
ALI_STEPS = 400


def _train(cfg: ModelConfig, steps: int, seed: int):
    zm = ZipfMarkov(vocab=VOCAB, seed=7)
    data = zm.batch_iter(16, 64, seed=seed)
    tc = TrainConfig(steps=steps, batch=16, seq_len=64,
                     optim=AdamWConfig(lr=1e-3, total_steps=steps))
    params, metrics = train_lm(cfg, data, tc, seed=seed, verbose=False)
    return params, metrics


def _load_cached(path: str, template):
    """Cached parameters, or None when the file is missing or was written
    for another architecture (then the caller retrains and overwrites it).
    Any other failure to read it propagates."""
    try:
        return ckpt.load(path, template)
    except FileNotFoundError:
        return None
    except ckpt.StaleCheckpoint as e:
        print(f"stale pair cache, retraining: {e}", file=sys.stderr)
        return None


def _get(cfg: ModelConfig, steps: int, seed: int, train: bool = True):
    path = os.path.join(CACHE_DIR, f"{cfg.name}.npz")
    template = jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    template = jax.tree.map(
        lambda s: jax.numpy.zeros(s.shape, s.dtype), template)
    cached = _load_cached(path, template)
    if cached is not None:
        return cached
    if not train:
        raise FileNotFoundError(f"no usable trained pair file at {path}")
    params, _ = _train(cfg, steps, seed)
    ckpt.save(path, params)
    return params


def _head_cache_key(cfg: ModelConfig, K: int, steps: int, seed: int) -> str:
    """Cache key for trained draft heads.  MUST hash the full head
    configuration — head count K AND the head architecture (d_model /
    vocab / norm-and-softcap settings of the base the heads read) — not
    just the base model's name: two head sets over the same base with a
    different K (or a base whose arch changed under the same name) are
    different parameter pytrees, and a stale .npz would either fail to
    load or, worse, silently restore mis-shaped heads."""
    arch = (f"{cfg.name}:L{cfg.num_layers}:d{cfg.d_model}"
            f":v{cfg.vocab_size}:eps{cfg.norm_eps}"
            f":cap{cfg.final_softcap}:K{K}:s{steps}:seed{seed}")
    return hashlib.sha256(arch.encode()).hexdigest()[:16]


def draft_heads_for(kind: str = "misaligned", K: int = 4,
                    steps: int = 200, seed: int = 11) -> dict:
    """Trained multi-position draft heads (DESIGN.md §7.12) for the draft
    model of ``get_pair(kind)``, cached under a key that hashes the head
    configuration (see _head_cache_key)."""
    dp, dcfg, _, _ = get_pair(kind)
    path = os.path.join(
        CACHE_DIR, f"heads-{_head_cache_key(dcfg, K, steps, seed)}.npz")
    template = jax.tree.map(
        lambda s: jax.numpy.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda: M.init_draft_heads(
            jax.random.PRNGKey(0), dcfg, K)))
    cached = _load_cached(path, template)
    if cached is not None:
        return cached
    zm = ZipfMarkov(vocab=VOCAB, seed=7)
    data = zm.batch_iter(16, 64, seed=seed)
    tc = TrainConfig(steps=steps, batch=16, seq_len=64,
                     optim=AdamWConfig(lr=1e-3, total_steps=steps))
    dhead, _ = train_draft_heads(dp, dcfg, data, K, tc, seed=seed,
                                 verbose=False)
    ckpt.save(path, dhead)
    return dhead


def get_pair(kind: str = "misaligned", steps: int = 400, *,
             train: bool = True
             ) -> Tuple[dict, ModelConfig, dict, ModelConfig]:
    """Returns (draft_params, draft_cfg, target_params, target_cfg).
    ``train=False`` only loads the cached files (the misaligned pair's are
    tracked in git) and raises FileNotFoundError where one is unusable."""
    if kind not in ("misaligned", "aligned"):
        raise ValueError(kind)
    tgt = _get(TARGET_CFG, 400, seed=0, train=train)
    if kind == "misaligned":
        dr = _get(DRAFT_MIS_CFG, MIS_STEPS, seed=6, train=train)
        return dr, DRAFT_MIS_CFG, tgt, TARGET_CFG
    dr = _get(DRAFT_ALI_CFG, ALI_STEPS, seed=6, train=train)
    return dr, DRAFT_ALI_CFG, tgt, TARGET_CFG


HYBRID_KINDS = ("falcon-shaped", "jamba-shaped")


def hybrid_pair(kind: str, seed: int = 0
                ) -> Tuple[dict, ModelConfig, dict, ModelConfig]:
    """Tiny random-init SSM-bearing draft/target pairs for the hybrid
    serving path (no training needed: greedy losslessness and rollback
    correctness are properties of the engine, not of model quality).

      * "falcon-shaped" — attention-free Mamba-1 stack (falcon-mamba-7b's
        family, arXiv:2410.05355);
      * "jamba-shaped"  — hybrid Mamba + attention with MoE FFNs
        (jamba-1.5's family).  Drop-free MoE capacity so outputs are
        batch-composition independent (reduced()'s convention).
    """
    common = dict(vocab_size=VOCAB, dtype="float32")
    if kind == "falcon-shaped":
        tcfg = ModelConfig(
            name="hy-falcon-t", family="ssm", num_layers=2, d_model=64,
            num_heads=2, num_kv_heads=1, d_ff=0,
            pattern=(("mamba", "none"),), **common)
        dcfg = ModelConfig(
            name="hy-falcon-d", family="ssm", num_layers=1, d_model=32,
            num_heads=2, num_kv_heads=1, d_ff=0,
            pattern=(("mamba", "none"),), **common)
    elif kind == "jamba-shaped":
        tcfg = ModelConfig(
            name="hy-jamba-t", family="hybrid", num_layers=2, d_model=64,
            num_heads=2, num_kv_heads=1, d_ff=256,
            pattern=(("mamba", "dense"), ("attn", "moe")),
            num_experts=4, num_experts_per_tok=2, moe_d_ff=64,
            capacity_factor=2.0, **common)
        dcfg = ModelConfig(
            name="hy-jamba-d", family="hybrid", num_layers=1, d_model=32,
            num_heads=2, num_kv_heads=1, d_ff=128,
            pattern=(("mamba", "dense"),), **common)
    else:
        raise ValueError(kind)
    tp = M.init_params(jax.random.PRNGKey(seed), tcfg)
    dp = M.init_params(jax.random.PRNGKey(seed + 1), dcfg)
    return dp, dcfg, tp, tcfg


LLAMA_KINDS = ("llama",)
# LLaMA-7B's 32 layers hold 13.5 GB of bf16 weights — too much for one
# 16 GB v5e beside the draft, the KV pools and activations.  One chip
# serves the first 16 layers; the other 16 would sit on a second
# pipeline stage.  Every width stays as published.
LLAMA_TARGET_LAYERS = 16


def llama_configs() -> Tuple[ModelConfig, ModelConfig]:
    """(draft, target) configs of the paper's headline pair, LLaMA-68M /
    LLaMA-7B (configs/paper_pairs.py), the target cut to
    LLAMA_TARGET_LAYERS layers."""
    draft, target, _c = PAPER_PAIRS["llama"]
    target = dataclasses.replace(
        target, name=f"{target.name}-{LLAMA_TARGET_LAYERS}l",
        num_layers=LLAMA_TARGET_LAYERS)
    return draft, target


def llama_pair(seed: int = 0
               ) -> Tuple[dict, ModelConfig, dict, ModelConfig]:
    """The LLaMA-68M / LLaMA-7B pair at published widths (target depth
    cut, see LLAMA_TARGET_LAYERS) with random bf16 weights from ``seed``.
    Weights are built under ``jit`` so each layer stack is written in
    place on the device (model.init_params)."""
    dcfg, tcfg = llama_configs()
    tp = jax.jit(lambda k: M.init_params(k, tcfg))(jax.random.PRNGKey(seed))
    dp = jax.jit(lambda k: M.init_params(k, dcfg))(
        jax.random.PRNGKey(seed + 1))
    return dp, dcfg, tp, tcfg


LOCAL_KINDS = ("gemma3-shaped",)


def local_pair(kind: str = "gemma3-shaped", seed: int = 0
               ) -> Tuple[dict, ModelConfig, dict, ModelConfig]:
    """Tiny random-init local-attention (sliding-window) draft/target pair
    for the batched serving path: gemma3's family — interleaved local
    (windowed ring cache) and global layers.  The window is deliberately
    smaller than prompt + generation so the ring wraps end to end during a
    serving test, exercising speculative overshoot + rollback against ring
    eviction (the `ring_slack` machinery of DESIGN.md §7.6)."""
    if kind != "gemma3-shaped":
        raise ValueError(kind)
    common = dict(vocab_size=VOCAB, dtype="float32", sliding_window=8)
    tcfg = ModelConfig(
        name="lo-gemma3-t", family="dense", num_layers=3, d_model=64,
        num_heads=2, num_kv_heads=1, d_ff=128, qk_norm=True,
        pattern=dense_pattern(2),            # 2 local : 1 global
        **common)
    dcfg = ModelConfig(
        name="lo-gemma3-d", family="dense", num_layers=1, d_model=32,
        num_heads=2, num_kv_heads=1, d_ff=64,
        pattern=(("local", "dense"),), **common)
    tp = M.init_params(jax.random.PRNGKey(seed), tcfg)
    dp = M.init_params(jax.random.PRNGKey(seed + 1), dcfg)
    return dp, dcfg, tp, tcfg


def measure_alpha(draft_params, draft_cfg, target_params, target_cfg,
                  n_prompts: int = 4, plen: int = 16, n_new: int = 48,
                  gamma: int = 4, seed: int = 0) -> float:
    """Empirical acceptance rate alpha = E[beta] under greedy target."""
    from repro.runtime.engines import EngineConfig, SpSEngine
    zm = ZipfMarkov(vocab=VOCAB, seed=7)
    eng = SpSEngine(draft_params, draft_cfg, target_params, target_cfg,
                    EngineConfig(gamma=gamma, temperature=0.0, max_len=1024))
    acc, tot = 0, 0
    for i, p in enumerate(zm.prompts(n_prompts, plen, seed=seed)):
        r = eng.generate(p, n_new, jax.random.PRNGKey(i))
        acc += r.stats.draft_tokens - r.stats.rollback_tokens
        tot += r.stats.draft_tokens
    return acc / max(tot, 1)
