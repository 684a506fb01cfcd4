"""Partition rules: FSDP("data") x TP("model") (+ "pod" = extra FSDP/DP).

Strategy (DESIGN.md §5, MaxText-style):
  * weight matrices: contraction/input dim sharded over the FSDP axes
    ("pod","data" when divisible), output/head/hidden dim over "model";
  * experts: stacked expert dim over "pod" when divisible (expert-FSDP),
    per-expert hidden over "model" (tensor-parallel experts) — the baseline;
    expert-parallel all-to-all is explored in the perf pass;
  * activations: batch over ("pod","data"); long_500k (batch=1) shards the
    KV-cache *sequence* over "data" instead (sequence parallelism);
  * every rule degrades gracefully: an axis is only used if it divides the
    dimension, so reduced smoke configs on 1 device shard nothing.

All functions return pytrees of ``jax.sharding.PartitionSpec`` matching the
params / cache / input pytrees.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig


def _axis_size(mesh: Mesh, name) -> int:
    if isinstance(name, tuple):
        return int(np.prod([_axis_size(mesh, n) for n in name]))
    return mesh.shape[name] if name in mesh.shape else 1


def _fit(mesh: Mesh, dim: int, *candidates):
    """First candidate axis (or axis tuple) that divides ``dim``; else None."""
    for cand in candidates:
        if cand is None:
            continue
        axes = tuple(a for a in (cand if isinstance(cand, tuple) else (cand,))
                     if a in mesh.shape)
        if not axes:
            continue
        if dim % _axis_size(mesh, axes) == 0:
            return axes if len(axes) > 1 else axes[0]
    return None


def fsdp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return fsdp_axes(mesh)


# ---------------------------------------------------------------------------
# parameter rules
# ---------------------------------------------------------------------------

def _leaf_spec(mesh: Mesh, path: Tuple[str, ...], shape: Tuple[int, ...],
               tp_only: bool = False) -> P:
    """Spec for one parameter leaf, given its key path (strings) and shape.

    ``shape`` excludes any leading period-stack axis (handled by caller).
    tp_only=True drops the FSDP axes (params replicated over "data",
    sharded over "model" only) — kills the per-microbatch FSDP weight
    all-gathers for models whose optimizer state fits (§Perf hillclimb C).
    """
    name = path[-1]
    fa = () if tp_only else fsdp_axes(mesh)
    def d0(dim):
        return _fit(mesh, dim, fa, None if tp_only else "data")

    def dm(dim):
        return _fit(mesh, dim, "model")

    if name in ("ln", "final_norm", "conv_b", "dt_b", "Dskip", "q_norm",
                "k_norm"):
        if name in ("conv_b", "dt_b", "Dskip") and len(shape) == 1:
            return P(dm(shape[0]))
        return P(*([None] * len(shape)))
    if name == "embed":                      # (V, D)
        return P(dm(shape[0]), d0(shape[1]))
    if name == "lm_head":                    # (D, V)
        return P(d0(shape[0]), dm(shape[1]))
    if name in ("wq", "wk", "wv"):           # (D, H*hd)
        return P(d0(shape[0]), dm(shape[1]))
    if name == "wo":                         # (H*hd, D)
        return P(dm(shape[0]), d0(shape[1]))
    if name in ("wg", "wu", "wd") and len(shape) == 3:
        # MoE (E, D, F) / (E, F, D): experts FSDP-shard over "pod" when
        # divisible; TP along D so the (E, C, D) dispatch buffer's
        # model-sharding contracts locally (§Perf It.7); the remaining dim
        # takes "data" only (never reuse an axis within one spec)
        e_ax = _fit(mesh, shape[0], "pod")
        d_dims = (dm(shape[1]), _fit(mesh, shape[2], "data")) \
            if name in ("wg", "wu") else \
            (_fit(mesh, shape[1], "data"), dm(shape[2]))
        return P(e_ax, *d_dims)
    if name in ("wg", "wu"):
        return P(d0(shape[0]), dm(shape[1]))
    if name == "wd":
        return P(dm(shape[0]), d0(shape[1]))
    if name == "router":                     # (D, E) — small, replicate
        return P(None, None)
    if name == "in_proj":                    # (D, 2E)
        return P(d0(shape[0]), dm(shape[1]))
    if name == "conv_w":                     # (Cv, E)
        return P(None, dm(shape[1]))
    if name == "x_db":                       # (E, R+2N)
        return P(dm(shape[0]), None)
    if name == "dt_w":                       # (R, E)
        return P(None, dm(shape[1]))
    if name == "A_log":                      # (E, N)
        return P(dm(shape[0]), None)
    if name == "out_proj":                   # (E, D)
        return P(dm(shape[0]), d0(shape[1]))
    return P(*([None] * len(shape)))


def params_specs(mesh: Mesh, cfg: ModelConfig, params_shape: Any,
                 tp_only: bool = False) -> Any:
    """PartitionSpec pytree for a params pytree (of ShapeDtypeStruct or
    arrays).  Handles the leading period-stack axis on "blocks" leaves."""

    def walk(node, path, stacked):
        if isinstance(node, dict):
            return {k: walk(v, path + (k,),
                            stacked or (k == "blocks")) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            t = type(node)
            return t(walk(v, path + (str(i),), stacked)
                     for i, v in enumerate(node))
        shape = tuple(node.shape)
        if stacked:
            spec = _leaf_spec(mesh, path, shape[1:], tp_only=tp_only)
            return P(None, *spec)
        return _leaf_spec(mesh, path, shape, tp_only=tp_only)

    return walk(params_shape, (), False)


# ---------------------------------------------------------------------------
# cache rules
# ---------------------------------------------------------------------------

def cache_specs(mesh: Mesh, cfg: ModelConfig, cache_shape: Any,
                *, shard_seq: bool = False,
                seq_axis: str = "data",
                batch_axis: str = "") -> Any:
    """Decode-cache specs.  Cache leaves are (stack, B, ...).

    shard_seq=True with seq_axis="data" (long_500k, batch 1): shard the KV
    sequence over "data" instead of the batch.  seq_axis="model" (decode
    hillclimb): sequence-parallel attention over the model axis — the
    query-side head sharding would otherwise force an all-gather of the
    whole cache per kv chunk (§Perf hillclimb A).
    """
    ba = batch_axes(mesh)

    def leaf(path, shape):
        name = path[-1]
        if batch_axis:
            b = _fit(mesh, shape[1], batch_axis)
        else:
            b = (_fit(mesh, shape[1], ba, "data")
                 if (not shard_seq or seq_axis == "model") else None)
        if name in ("k", "v"):               # (stack, B, S, KV, hd)
            s = _fit(mesh, shape[2], seq_axis) if shard_seq else None
            if batch_axis or (shard_seq and seq_axis == "model"):
                kv = hd = None                # heads stay local
            else:
                kv = _fit(mesh, shape[3], "model")
                hd = None if kv else _fit(mesh, shape[4], "model")
            return P(None, b, s, kv, hd)
        if name == "pos":                    # (stack, B, S)
            s = _fit(mesh, shape[2], seq_axis) if shard_seq else None
            return P(None, b, s)
        if name == "conv":                   # (stack, B, Cv-1, E)
            return P(None, b, None, _fit(mesh, shape[3], "model"))
        if name == "ssm":                    # (stack, B, E, N)
            return P(None, b, _fit(mesh, shape[2], "model"), None)
        return P(*([None] * len(shape)))

    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            t = type(node)
            return t(walk(v, path + (str(i),)) for i, v in enumerate(node))
        return leaf(path, tuple(node.shape))

    return walk(cache_shape, ())


def tokens_spec(mesh: Mesh, batch: int) -> P:
    return P(_fit(mesh, batch, batch_axes(mesh), "data"), None)


# ---------------------------------------------------------------------------
# serving cache rules (decode_state component layouts)
# ---------------------------------------------------------------------------

def serving_cache_specs(mesh: Mesh, cfg: ModelConfig, cache_shape: Any,
                        *, batch_axis: str = "") -> Any:
    """Specs for a serving DecodeState cache pytree (DESIGN.md §7.10).

    Unlike the training ``cache_specs`` (leaves are uniformly
    (stack, B, S, ...)), a serving cache is a *mixed* component tree:

      * dense attention rows ``k``/``v`` (stack, n_rows, S, KV, hd) and
        ``pos`` (stack, n_rows, S): rows shard over ``batch_axis`` (the
        dense backend's data parallelism), KV heads over "model"
        (head_dim when the KV count doesn't divide);
      * paged attention ``k_pages``/``v_pages`` (stack, num_pages + 1,
        KV, page_size, hd): the page axis stays UNSHARDED — every device
        holds the head-shard of every logical page, so a page id ``p``
        names the (device, p) pair family and the host page tables
        replicate verbatim.  KV heads (else head_dim) shard over "model";
      * SSM checkpoint rings ``h_ring`` (stack, n_rows, ring, E, N) /
        ``conv_ring`` (stack, n_rows, ring, Cv-1, E): rows over
        ``batch_axis``, the expanded state dim E over "model" (matching
        the tp params rules for in_proj/out_proj).

    Every rule degrades through ``_fit``: an axis is used only when it
    divides the dimension, so a 1x1 mesh (or an odd batch) shards nothing.
    """

    def heads_spec(kv_dim, hd_dim):
        kv = _fit(mesh, kv_dim, "model")
        hd = None if kv else _fit(mesh, hd_dim, "model")
        return kv, hd

    def leaf(path, shape):
        name = path[-1]
        b = _fit(mesh, shape[1], batch_axis) if batch_axis else None
        if name in ("k", "v"):               # (stack, B, S, KV, hd)
            kv, hd = heads_spec(shape[3], shape[4])
            return P(None, b, None, kv, hd)
        if name == "pos":                    # (stack, B, S)
            return P(None, b, None)
        if name in ("k_pages", "v_pages"):   # (stack, P+1, KV, ps, hd)
            kv, hd = heads_spec(shape[2], shape[4])
            return P(None, None, kv, None, hd)
        if name == "h_ring":                 # (stack, B, ring, E, N)
            return P(None, b, None, _fit(mesh, shape[3], "model"), None)
        if name == "conv_ring":              # (stack, B, ring, Cv-1, E)
            return P(None, b, None, None, _fit(mesh, shape[4], "model"))
        return P(*([None] * len(shape)))

    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            t = type(node)
            return t(walk(v, path + (str(i),)) for i, v in enumerate(node))
        return leaf(path, tuple(node.shape))

    return walk(cache_shape, ())


def named(mesh: Mesh, spec_tree: Any) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
