"""Device-resident inner loop for the batched serving engines
(DESIGN.md §7.7).

The PR 1 engines serialized every decode step through the host: each
batched forward ended in a ``jax.device_get`` of the full (B, T, V) logits
and verification/residual sampling ran in float64 numpy per row, so draft,
target and verdict could never overlap and the logits transfer alone
dwarfed the verify FLOPs.  This module is the replacement: a small set of
jitted, shape-stable functions that keep every distribution on device and
hand the host only small int32/f32 *packets* (sampled tokens, confidence
signals, accept lengths, branch verdicts).

Design rules:

  * **Packets, not logits.**  Every function returns either device arrays
    that feed the next device call (logits, q-distribution slices) or a
    packed (B, k) array of a few int32/f32 per row — the only thing the
    engine ever fetches.
  * **Shape stability.**  All row-index / counter arrays are padded to the
    decoder's static row count and token widths are padded to the bucket
    ladder (``bucket``), so the jit cache holds a handful of traces no
    matter how H-RAD's adaptive gamma staggers per-request chunk lengths.
    Pad lanes compute garbage that the host ignores; pad draws consume
    uniforms at counter coordinates the real stream never visits.
  * **Folded-key determinism.**  Uniforms come from
    ``sampling.uniform_grid``: element (s, j) is a pure function of
    (rid_s, ctr_s + j), and the engine advances each request's counter by
    its OWN consumption (its chunk length, its branch count) — never by a
    padded width — so sampled streams are batch-composition independent.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.kernels import verify_accept as _va
from repro.models import layers as _L
from repro.obs import trace as _obs
from repro.runtime import sampling as S

__all__ = ["bucket", "prefill_bucket", "prefill_rungs", "kernel_route",
           "tick_sample",
           "draft_chunk", "masked_token_column", "compose_verify_tokens",
           "sps_verify", "draw_cands", "branch_verify",
           "set_trace_annotations"]

# The switch of the program's ``engine.*`` profiler spans (obs/trace.py,
# DESIGN.md §7.9): named ranges in the profiler's own trace, on the same
# clock as the device's operations, free while off.  Re-exported here for
# the callers that have always turned the spans on from this module (the
# on-chip benchmark's traced run); launch/serve.py --profile-dir turns
# them on through obs.
set_trace_annotations = _obs.set_trace_annotations


def _replicated(tree, mesh):
    """Pin host-packet outputs fully replicated on ``mesh`` (DESIGN.md
    §7.10).  The serving loop's device -> host boundary is a handful of
    tiny int32/f32 packets per round; replicating them makes the fetch a
    local read on every shard and keeps GSPMD from threading a packet's
    layout back into the verify partitioning.  ``mesh=None`` (the
    single-device paths and every pre-mesh caller) is a no-op."""
    if mesh is None:
        return tree
    s = NamedSharding(mesh, PartitionSpec())
    return jax.tree.map(
        lambda x: jax.lax.with_sharding_constraint(x, s), tree)


def bucket(n: int) -> int:
    """Round a token width up the fixed ladder 1/2/4/8/... so adaptive
    draft lengths never retrace the jitted step functions."""
    b = 1
    while b < n:
        b *= 2
    return b


def prefill_bucket(n: int, quantum: int) -> int:
    """Prefill length ladder: round a prompt length up to a multiple of
    ``quantum``.  Decode widths ride the power-of-two ``bucket`` ladder,
    but prompt lengths are unbounded — power-of-two padding could overshoot
    by max_len/2, far past the ring_slack / checkpoint-ring margins that
    make ahead-of-length pad writes safe.  A fixed quantum bounds the pad
    span to ``quantum - 1`` (a margin the serving engines add to their
    rings) while still collapsing arbitrary prompt lengths onto one
    compiled trace per rung instead of one per distinct length."""
    assert quantum > 0
    return max(quantum, -(-n // quantum) * quantum)


def prefill_rungs(lengths, quantum: int):
    """Distinct prefill-ladder rungs (sorted ascending) a set of prompt /
    suffix lengths lands on — the number of prefill forwards an admission
    group costs per decoder when every group member fits one lanes-chunk.
    Tests and the prefix-cache bench use this to pin "cached admissions
    run only the uncached suffix rungs" as an exact call count."""
    return sorted({prefill_bucket(n, quantum) for n in lengths if n > 0})


def kernel_route(ttemp: float, dtemp: float) -> bool:
    """Should the fused verify run through the batched Pallas
    ``verify_accept`` kernel?  True on TPU with both temperatures > 0 (the
    kernel softmaxes pre-scaled logits; temp 0 needs the one-hot probs
    path), overridable via REPRO_VERIFY_BACKEND=pallas|xla.  Off-TPU the
    compiled XLA twin is the production route — interpret mode would
    re-add the overhead the device-resident loop removes."""
    if ttemp <= 0.0 or dtemp <= 0.0:
        return False                  # one-hot probs need the XLA path
    env = os.environ.get("REPRO_VERIFY_BACKEND")
    if env == "pallas":
        return True
    if env == "xla":
        return False
    return jax.default_backend() == "tpu"


def _chain_via_kernel(p_lg: jax.Array, q_lg: jax.Array, toks: jax.Array,
                      lens: jax.Array, ugrid: jax.Array, interpret: bool):
    """Chain verdict through the batched (B, R, V) Pallas kernel:
    temperature-prescaled LOGITS in, accept flags + per-position residual
    samples out, then the same leading-run reduction as
    ``sampling.verify_chain_device``.  The residual draw reuses the
    chain's final uniform (``ugrid[s, lens[s]]``, the numpy cores'
    ``us[-1]``) broadcast as the kernel's per-position ``w`` so the sample
    at the rejection position matches the oracle's layout."""
    R = toks.shape[1]
    u_fin = jnp.take_along_axis(ugrid, lens[:, None].astype(jnp.int32),
                                1)[:, 0]
    w = jnp.broadcast_to(u_fin[:, None], (toks.shape[0], R))
    acc, res, _, _ = _va.verify_accept_batched(
        p_lg, q_lg, toks, lens, ugrid[:, :R], w, interpret=interpret)
    j = jnp.arange(R, dtype=jnp.int32)[None]
    within = j < lens[:, None]
    run = jnp.cumprod(jnp.where(within, acc, 1), axis=1)
    n_acc = (run * within.astype(jnp.int32)).sum(1).astype(jnp.int32)
    all_acc = n_acc == lens
    nxt = jnp.take_along_axis(
        res, jnp.minimum(n_acc, R - 1)[:, None], 1)[:, 0]
    nxt = jnp.where(all_acc, -1, nxt).astype(jnp.int32)
    return n_acc, nxt, all_acc


@functools.partial(jax.jit, static_argnames=("dtemp", "stemp", "mesh"))
def tick_sample(lg: jax.Array, last: jax.Array, rids: jax.Array,
                ctrs: jax.Array, base_key, *, dtemp: float, stemp: float,
                mesh=None):
    """One fused draft-sampling tick over a batched forward's logits.

    All arrays are indexed BY DECODER ROW: lg (n_rows, T, V) logits,
    last/rids/ctrs (n_rows,) — last-real-token index and PRNG coordinates
    of the request occupying each row (rows without a sampling request
    carry (0, 0) and compute garbage the host ignores).

    Returns (tokens (n_rows,) i32 device — chained into the next ingest
    without visiting the host, q_slice (n_rows, V) raw logits device — the
    q distributions verification will consume, packed (n_rows, 2) f32
    [token, signal-confidence] — the per-tick host packet for the engines'
    stop rules and commit bookkeeping).
    """
    sl = jnp.take_along_axis(
        lg, last.astype(jnp.int32)[:, None, None], 1)[:, 0]   # (n_rows, V)
    qp = S.probs_from_logits(sl, dtemp)
    sg = S.probs_from_logits(sl, stemp)
    u = S.uniform_grid(base_key, rids, ctrs, 1)[:, 0]
    tok = S.categorical_from_uniform(qp, u)
    packed = jnp.stack([tok.astype(jnp.float32), sg.max(-1)], axis=-1)
    tok, packed = _replicated((tok, packed), mesh)
    return tok, sl, packed


@functools.partial(jax.jit, static_argnames=("g", "dtemp", "stemp", "eps",
                                             "cap", "mesh"))
def draft_chunk(lg: jax.Array, feats: jax.Array, final_norm: jax.Array,
                heads: jax.Array, last: jax.Array, rids: jax.Array,
                ctrs: jax.Array, base_key, *, g: int, dtemp: float,
                stemp: float, eps: float = 1e-6, cap=None, mesh=None):
    """One fused parallel-draft chunk — ``tick_sample``'s single-dispatch
    twin (DESIGN.md §7.12).  Consumes ONE draft forward that ingested each
    row's pending tokens plus ``g`` masked draft slots.

    All arrays are indexed BY DECODER ROW: lg (n_rows, T, V) the forward's
    logits, feats (n_rows, T, D) its final-layer (pre-final-norm) hidden
    states, last (n_rows,) the last REAL token column — slot j (1..g) rides
    at column ``last + j``.  final_norm (D,) and heads (K, D, V) are the
    draft model's norm scale and the multi-token head stack (K >= g).

    Distribution layout: entry 0 is the AR distribution at ``last`` —
    exactly sequential tick 1's distribution — and entry i (1 <= i <= g) is
    head i applied to slot i's hidden state.  Chunk token i is sampled from
    entry i-1 with the uniform at counter offset i-1: the SAME (rid, ctr)
    coordinates g sequential ticks would consume, so the engine advances
    each row's counter by its own chunk length exactly as before and
    verification's uniform block is untouched.  Tokens are independent
    given the prefix (entry i never sees tokens 1..i-1) — that is the draft
    *distribution* difference parallel mode is allowed; the verifier
    consumes q_stack unchanged and stays lossless.

    Returns (tok_stack (g, n_rows) i32 device, q_stack (g+1, n_rows, V) f32
    raw logits device — entries 0..g-1 feed ``sps_verify``/``branch_verify``
    unchanged, entry g is the next-position signal distribution (SpecBranch
    q_b / branch-lane final signal), packed (n_rows, g+1, 2) f32
    [token, signal-confidence] — the one host packet for stop rules; row g
    carries (-1, conf) since entry g is never sampled).
    """
    n = lg.shape[0]
    ar = jnp.take_along_axis(
        lg, last.astype(jnp.int32)[:, None, None], 1)[:, 0]     # (n, V)
    j = jnp.arange(1, g + 1, dtype=jnp.int32)[None]
    sidx = jnp.clip(last.astype(jnp.int32)[:, None] + j, 0,
                    feats.shape[1] - 1)
    hs = jnp.take_along_axis(feats, sidx[..., None], 1)         # (n, g, D)
    hn = _L.rms_norm(hs, final_norm, eps)
    hlg = jnp.einsum("ngd,gdv->ngv", hn.astype(jnp.float32),
                     heads[:g].astype(jnp.float32))
    hlg = _L.softcap(hlg, cap)
    q_all = jnp.concatenate([ar.astype(jnp.float32)[:, None], hlg], axis=1)
    qp = S.probs_from_logits(q_all[:, :g], dtemp)               # (n, g, V)
    u = S.uniform_grid(base_key, rids, ctrs, g)                 # (n, g)
    tok = S.categorical_from_uniform(qp, u)                     # (n, g)
    conf = S.probs_from_logits(q_all, stemp).max(-1)            # (n, g+1)
    tokf = jnp.concatenate(
        [tok.astype(jnp.float32), jnp.full((n, 1), -1.0, jnp.float32)], 1)
    packed = jnp.stack([tokf, conf], axis=-1)                   # (n, g+1, 2)
    tok_stack, packed = _replicated((tok.T, packed), mesh)
    return tok_stack, q_all.transpose(1, 0, 2), packed


@jax.jit
def masked_token_column(tokens: jax.Array, mask: jax.Array):
    """(n_rows,) sampled tokens -> (n_rows, 1) step input with non-ingesting
    rows zeroed (their write head parks in place; the pad write is causally
    masked, see BatchedDecoder)."""
    return jnp.where(mask, tokens.astype(jnp.int32), 0)[:, None]


@functools.partial(jax.jit, static_argnames=("n_rows", "Tb"))
def compose_verify_tokens(pend: jax.Array, npend: jax.Array,
                          tok_stack: jax.Array, drows: jax.Array,
                          trows: jax.Array, *, n_rows: int, Tb: int):
    """Target-verify step input: row s holds pend[s] ++ drafted[s] padded to
    the Tb bucket, scattered into the target decoder's (n_rows, Tb) frame.

    pend: (S, P) host-staged pending tokens; npend: (S,); tok_stack:
    (g, n_draft_rows) the draft ticks' sampled tokens (device, never
    fetched); drows/trows: (S,) draft/target row per lane.
    """
    S_, P = pend.shape
    g = tok_stack.shape[0]
    drafted = tok_stack[:, drows].T.astype(jnp.int32)     # (S, g)
    t = jnp.arange(Tb, dtype=jnp.int32)[None]
    pidx = jnp.broadcast_to(jnp.clip(t, 0, P - 1), (S_, Tb))
    didx = jnp.clip(t - npend[:, None], 0, g - 1)
    vals = jnp.where(t < npend[:, None],
                     jnp.take_along_axis(pend.astype(jnp.int32), pidx, 1),
                     jnp.take_along_axis(drafted, didx, 1))
    full = jnp.zeros((n_rows, Tb), jnp.int32)
    return full.at[trows].set(vals)


@functools.partial(jax.jit,
                   static_argnames=("g", "ttemp", "dtemp", "kernel",
                                    "interpret", "mesh"))
def sps_verify(tlg: jax.Array, q_stack: jax.Array, tok_stack: jax.Array,
               trows: jax.Array, drows: jax.Array, npend: jax.Array,
               rids: jax.Array, ctrs: jax.Array, base_key, glens=None, *,
               g: int, ttemp: float, dtemp: float, kernel: bool = False,
               interpret: bool = True, mesh=None):
    """Fused SpS verification: target-forward logits in, one small packet
    out.  tlg: (n_rows, Tb, V); q_stack: (g, n_draft_rows, V) raw draft
    logits from the ticks; tok_stack: (g, n_draft_rows).

    ``glens`` (S,) i32, optional: per-row REAL draft lengths <= g, for the
    history predictor's per-request adaptive gamma — row s chain-verifies
    only its own glens[s] tokens, takes its bonus distribution at position
    glens[s], and consumes glens[s] + 1 uniforms (so PRNG streams stay
    batch-composition independent).  ``None`` (every pre-predictor caller)
    is the uniform-g path, trace-identical to before the parameter existed.

    ``kernel=True`` (see ``kernel_route``) sends the accept/residual pass
    through the batched Pallas ``verify_accept`` kernel on
    temperature-prescaled logits; otherwise the compiled XLA twin in
    ``sampling.verify_chain_device`` runs the same math in probs space.

    Returns packet (S, 3 + g) i32: [n_acc, next_token, all_acc,
    drafted tokens...] — accept lengths, the resampled/bonus token and the
    draft tokens the host has never seen, ~4(3+g) bytes per request instead
    of 4V(T+g).
    """
    rowlg = tlg[trows]                                    # (S, Tb, V)
    j = jnp.arange(g + 1, dtype=jnp.int32)[None]
    idx = jnp.clip(npend[:, None] - 1 + j, 0, rowlg.shape[1] - 1)
    pall = jnp.take_along_axis(rowlg, idx[..., None], 1)  # (S, g+1, V)
    q_raw = q_stack[:, drows].transpose(1, 0, 2)          # (S, g, V)
    drafted = tok_stack[:, drows].T.astype(jnp.int32)     # (S, g)
    ugrid = S.uniform_grid(base_key, rids, ctrs, g + 1)
    if glens is None:
        lens = jnp.full((drafted.shape[0],), g, jnp.int32)
        bonus_lg = pall[:, g]
    else:
        lens = jnp.clip(glens.astype(jnp.int32), 0, g)
        bonus_lg = jnp.take_along_axis(
            pall, lens[:, None, None], 1)[:, 0]
    bonus = S.probs_from_logits(bonus_lg, ttemp)
    if kernel:
        n_acc, nxt, all_acc = _chain_via_kernel(
            pall[:, :g] / ttemp, q_raw / dtemp, drafted, lens, ugrid,
            interpret)
        u_fin = jnp.take_along_axis(ugrid, lens[:, None], 1)[:, 0] \
            if glens is not None else ugrid[:, g]
        nxt = jnp.where(all_acc, S.categorical_from_uniform(bonus, u_fin),
                        nxt)
    else:
        n_acc, nxt, all_acc = S.verify_chain_device(
            S.probs_from_logits(pall[:, :g], ttemp),
            S.probs_from_logits(q_raw, dtemp), drafted, lens, ugrid, bonus)
    return _replicated(jnp.concatenate(
        [n_acc[:, None], nxt[:, None], all_acc.astype(jnp.int32)[:, None],
         drafted], axis=1), mesh)


@functools.partial(jax.jit, static_argnames=("K", "stemp", "mode", "mesh"))
def draw_cands(qb_lg: jax.Array, rids: jax.Array, ctrs: jax.Array,
               base_key, *, K: int, stemp: float, mode: str, mesh=None):
    """Branch-point candidates from the stored q_b signal logits (S, V).
    mode="sample": K i.i.d. inverse-CDF draws at counter offsets 0..K-1 (a
    row with adaptive k consumes only its first k); "topk": deterministic
    Top-K.  Returns (S, K) int32."""
    if mode == "topk":
        _, idx = jax.lax.top_k(qb_lg, K)
        return _replicated(idx.astype(jnp.int32), mesh)
    qb = S.probs_from_logits(qb_lg, stemp)
    ugrid = S.uniform_grid(base_key, rids, ctrs, K)
    return _replicated(
        S.categorical_from_uniform(qb[:, None, :], ugrid), mesh)


@functools.partial(jax.jit,
                   static_argnames=("CH", "K", "ttemp", "dtemp", "stemp",
                                    "kernel", "interpret", "mesh"))
def branch_verify(tlg: jax.Array, trows: jax.Array, npend: jax.Array,
                  gch: jax.Array, chunk_q: jax.Array, chunk_toks: jax.Array,
                  cands: jax.Array, ks: jax.Array, qb_lg: jax.Array,
                  rids: jax.Array, ctrs: jax.Array, base_key, *,
                  CH: int, K: int, ttemp: float, dtemp: float, stemp: float,
                  kernel: bool = False, interpret: bool = True, mesh=None):
    """Fused SpecBranch verdict: chain-verify each request's chunk (ragged
    lengths gch <= CH) AND run Algorithm 2 over its branch candidates, all
    from one target forward's logits.

    chunk_q: (S, CH, V) raw draft logits of the chunk; chunk_toks: (S, CH);
    cands: (S, K); ks: (S,) real candidate counts; qb_lg: (S, V) branch-
    point signal logits.  Uniform layout per request: indices [0, gch] for
    the chain (ragged, own length), [CH + 1, CH + 1 + ks] for the branch
    stage — both blocks are addressed by the request's own lengths, so
    consumption is pad-independent.

    Returns packet (S, 5) i32: [n_acc, chain_next, all_acc,
    accepted_branch, branch_token].
    """
    rowlg = tlg[trows]
    j = jnp.arange(CH + 1, dtype=jnp.int32)[None]
    idx = jnp.clip(npend[:, None] - 1 + j, 0, rowlg.shape[1] - 1)
    lall = jnp.take_along_axis(rowlg, idx[..., None], 1)   # (S, CH+1, V)
    pall = S.probs_from_logits(lall, ttemp)
    p_b = jnp.take_along_axis(
        pall, gch[:, None, None].astype(jnp.int32), 1)[:, 0]   # (S, V)
    W = CH + 1 + K + 1
    ugrid = S.uniform_grid(base_key, rids, ctrs, W)
    if CH == 0:
        S_ = trows.shape[0]
        n_acc = jnp.zeros((S_,), jnp.int32)
        nxt = jnp.full((S_,), -1, jnp.int32)
        all_acc = jnp.ones((S_,), bool)
    elif kernel:
        n_acc, nxt, all_acc = _chain_via_kernel(
            lall[:, :CH] / ttemp, chunk_q / dtemp, chunk_toks, gch,
            ugrid[:, :CH + 1], interpret)
    else:
        n_acc, nxt, all_acc = S.verify_chain_device(
            pall[:, :CH], S.probs_from_logits(chunk_q, dtemp), chunk_toks,
            gch, ugrid[:, :CH + 1], None)
    qb_probs = S.probs_from_logits(qb_lg, stemp)
    acc_b, tok_b = S.branch_verdict_device(p_b, qb_probs, cands, ks,
                                           ugrid[:, CH + 1:])
    return _replicated(jnp.stack([n_acc, nxt, all_acc.astype(jnp.int32),
                                  acc_b, tok_b], axis=1), mesh)
