"""Batched serving engines: SpS and SpecBranch draft/verify rounds run
across a whole batch of requests (DESIGN.md §7.2, §7.7).

``BatchedDecoder`` is the substrate: one model with an N-row decode cache
and *per-row* positions, so requests at different sequence lengths share
every forward call.  Rows are independent under attention (the causal mask
is position-driven and the cache is written at per-row slots), which gives
three properties the serving layer builds on:

  * multi-token rows of different lengths batch by padding — pad writes land
    beyond a row's logical length and are causally masked until overwritten
    (the runner's positional-rollback model, DESIGN.md §3);
  * per-request rollback is positional: shrink the row's logical length and
    reclaim the pages of the rejected tokens (kv_pool) — no cache copies;
  * SpecBranch branch forks are extra draft rows plus copy-on-write page
    sharing in the pool, not batch-axis cache replication.

Engine contract: per-request token streams are distributed exactly as the
sequential engines (lossless; token-for-token identical under a greedy
target).  The inner loop is **device-resident** (DESIGN.md §7.7): every
distribution — draft q, target p, residuals, branch posteriors — lives and
is consumed on device through the jitted functions in serving/device_loop,
and the host receives only small int32/f32 packets (sampled tokens,
confidence signals, accept lengths, branch verdicts).  Uniform randomness
comes from per-request folded PRNG keys indexed by a per-request decision
counter, so a request's output is independent of which batch it rode in —
the same batch-composition-independence contract the PR 1 host-side
float64 numpy path provided (that path survives in runtime/sampling.py as
the oracle for the sequential engines and the equivalence tests).

Token widths are padded up a fixed bucket ladder (1/2/4/8/...), so H-RAD's
adaptive chunk lengths never retrace the jitted step; and a SpecBranch
round dispatches its target verification *before* running its draft ticks,
so on an asynchronous-dispatch backend the drafting hides under the
verification — the paper's branch parallelism realized at the dispatch
layer.  Within the draft phase the per-tick [token, conf] packet is
double-buffered: tick t's computation is dispatched before tick t-1's
packet is fetched, with stop decisions applied one tick late (a row that
should have stopped pruned its one optimistically ingested token the same
way any rollback does), so the draft loop's only blocking fetch overlaps
drafting too.

Admission runs **batched bucketed prefill** (DESIGN.md §7.8): requests
admitted in the same round are grouped onto a prefill length ladder
(multiples of a fixed quantum, sized inside the rings' slack margins so
padding can never wrap live window or checkpoint state) and each bucket is
ingested with ONE forward at a fixed lane count — killing both the
per-request admission stall and the one-trace-per-prompt-length retrace.

Cost accounting (Group SD, App. G.4): a round's draft steps are batched
over rows and its target verify is ONE batched call, priced the same as a
single-request call because decode-time target forwards are memory-bound.
A SpS round is serial like its sequential counterpart
(``draft_steps * t + c * t``); a SpecBranch round with branch-stage
requests overlaps drafting with verification
(``max(draft_steps * t, c * t)``).  The batching win is amortization:
one target-call price per round covers every request in the batch.

SSM/hybrid models batch too (DESIGN.md §7.6): every mamba slot carries a
position-indexed checkpoint ring (``init_cache(..., ssm_ring=...)``) that
snapshots the post-step recurrent carry per drafted position, so per-row
rollback is the same positional reset as attention — shrink the logical
length and the next forward resumes from the accept-point checkpoint,
O(1), no replay.  Pad writes land on future checkpoint slots and are
overwritten before any load, the recurrent twin of causally-masked pad KV.

Storage backends ride the DecodeState component layer (DESIGN.md §7.8):
``attn_backend="dense"`` keeps the N-row reference caches; ``"paged"``
stores attention KV physically scattered across per-decoder page pools
(split id spaces, so each buffer is sized to its own pool) and attends in
place through the page tables (Pallas paged-attention kernel, DESIGN.md
§7.5) — same token streams, no gather, zero-copy branch forks and
rollback, and preemption swap packed straight from the pages.  SSM/hybrid
configs serve on BOTH backends: their mamba slots carry per-row checkpoint
rings in a mixed pytree next to the (dense or paged) attention slots, and
on the paged backend a preempted hybrid row swaps as paged token rows plus
one explicit ring checkpoint.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hrad as H
from repro.kernels.ops import default_interpret
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.obs.trace import NULL_RECORDER, annotate
from repro.runtime import predictor as PRED
from repro.runtime import sampling as S
from repro.runtime.cost_model import CostModel
from repro.runtime.engines import EngineConfig, GenResult, GenStats
from repro.serving import device_loop as DL
from repro.serving.decode_state import DecodeState
from repro.serving.kv_pool import (PagedKVPool, PagedStore, PoolExhausted,
                                   PoolGroup)
from repro.serving.prefix_cache import PrefixCache


def _count_fetch(owner, arr) -> np.ndarray:
    """THE device -> host gate of the serving layer: every byte that
    crosses the boundary goes through here and lands in ``owner``'s
    ``xfer_bytes``/``xfer_fetches`` tally (decoder or engine) — the
    counters the metrics report and the CI transfer baseline compare.
    Each call is one ``engine.fetch`` profiler span: the host blocked on
    the device."""
    with annotate("engine.fetch"):
        a = np.asarray(jax.device_get(arr))
    owner.xfer_bytes += a.nbytes
    owner.xfer_fetches += 1
    return a


def _spanned(name: str):
    """Run the decorated method inside the profiler span ``name``
    (``obs/trace.py::annotate``: free while the spans are off)."""
    def wrap(fn):
        @functools.wraps(fn)
        def spanned(*args, **kw):
            with annotate(name):
                return fn(*args, **kw)
        return spanned
    return wrap


# ---------------------------------------------------------------------------
# multi-row decoder
# ---------------------------------------------------------------------------

class BatchedDecoder:
    """One model + an N-row decode cache with per-row positions.

    The engine owns per-row logical lengths; the decoder is a thin compute
    wrapper around a ``DecodeState`` (serving/decode_state.py): ``step``
    runs one batched forward at caller-supplied per-row start positions and
    returns DEVICE logits (nothing is fetched — the device-resident loop
    consumes them in place), ``prefill_rows`` ingests a GROUP of prompts
    into fresh rows with one forward per prefill-ladder bucket, and every
    state operation — fork, bind, rollback, swap pack/unpack, ring
    snapshot/restore — delegates to the state's components, so nothing
    here branches on the storage layout.  ``xfer_bytes`` counts every byte
    this decoder moves device -> host (swap packing, ring snapshots) for
    the serving transfer metrics.

    Storage layouts (DESIGN.md §7.5, §7.6, §7.8) are the DecodeState
    components: dense N-row attention caches, physically paged attention
    addressed through kv_pool page tables (``paged=pool``), and per-row
    SSM checkpoint rings — mixed freely, so hybrid configs run on either
    attention backend.

    Batched bucketed prefill: ``prefill_rows`` pads each admission group's
    prompts up a fixed-quantum length ladder (``DL.prefill_bucket``) at a
    fixed lane count, so admitting k same-bucket requests costs ONE
    forward and ONE compiled trace.  Pad tokens land beyond a row's
    logical length (causally masked / trash-paged until overwritten) and
    the quantum is bounded by the rings' slack margins, so prefill padding
    can never wrap live window or checkpoint state.
    """

    def __init__(self, params, cfg: ModelConfig, *, n_rows: int,
                 max_len: int, paged: Optional[PagedKVPool] = None,
                 ssm_ring: int = 0, prefill_lanes: int = 0,
                 prefill_quantum: int = 8, mesh=None):
        self.cfg = cfg
        self.n_rows, self.max_len = n_rows, max_len
        self.paged = paged
        self.mesh = mesh
        # mesh-sharded serving (DESIGN.md §7.10): params shard
        # tensor-parallel over "model" (replicated over "data" — tp_only
        # keeps decode free of FSDP weight all-gathers), dense cache rows
        # ride "data" when divisible, paged page buffers shard their KV
        # heads only.  Activations/logits pin batch-over-"data" and stay
        # head/vocab-UNsharded, so each forward's collective contract is
        # the TP set alone (pinned by tests/test_sharded_serving.py).
        act_spec = logits_spec = None
        paged_backend = None
        if mesh is not None:
            from repro.sharding import rules as _rules
            params = jax.device_put(
                params, _rules.named(mesh, _rules.params_specs(
                    mesh, cfg, params, tp_only=True)))
            from jax.sharding import NamedSharding, PartitionSpec as P
            b_ax = (None if paged is not None
                    else _rules._fit(mesh, n_rows, "data"))
            act_spec = NamedSharding(mesh, P(b_ax, None, None))
            logits_spec = NamedSharding(mesh, P(b_ax, None, None))
            if _rules._axis_size(mesh, "model") > 1:
                # the Pallas paged kernel is a custom call GSPMD cannot
                # partition — route the paged forward to the XLA twin
                paged_backend = "xla"
        self.params = params
        # checkpoint-ring depth for mamba slots AND window slack for local
        # attention rings — both bound how far ahead of a row's logical
        # length writes may land (bucket-ladder padding, prefill padding)
        self.ssm_ring = max(0, ssm_ring)
        self.state = DecodeState(cfg, n_rows=n_rows, max_len=max_len,
                                 paged=paged, ssm_ring=self.ssm_ring,
                                 mesh=mesh)
        self.prefill_lanes = prefill_lanes or DL.bucket(n_rows)
        self.prefill_quantum = prefill_quantum
        self.prefill_shapes: set = set()
        self.n_calls = 0
        self.n_call_tokens = 0
        self.xfer_bytes = 0
        self.xfer_fetches = 0
        state = self.state

        if paged is not None:
            # the paged buffers are pool-sized; donate them so a step (or
            # a single-page COW copy) updates in place instead of
            # materializing a full pool copy per call — self.cache is
            # rebound to the result immediately, so donation is safe
            @functools.partial(jax.jit, donate_argnums=(1,))
            def _fwd_paged(params, cache, tokens, pos, table, lens):
                positions = pos[:, None] + jnp.arange(
                    tokens.shape[1], dtype=jnp.int32)[None]
                logits, cache, aux = M.forward(
                    params, cfg, tokens, cache=cache, positions=positions,
                    feature_mode="all", paged=(table, lens),
                    act_spec=act_spec, logits_spec=logits_spec,
                    paged_backend=paged_backend)
                return logits, cache, aux["features"]

            @functools.partial(jax.jit, donate_argnums=(1,))
            def _prefill_paged(params, cache, tokens, table, lens, rows):
                lanes, T = tokens.shape
                sub = state.prefill_view(cache, lanes)
                positions = jnp.broadcast_to(
                    jnp.arange(T, dtype=jnp.int32)[None], (lanes, T))
                logits, sub, aux = M.forward(
                    params, cfg, tokens, cache=sub, positions=positions,
                    feature_mode="all", paged=(table, lens),
                    act_spec=act_spec, logits_spec=logits_spec,
                    paged_backend=paged_backend)
                return (logits, state.prefill_merge(cache, sub, rows),
                        aux["features"])

            @functools.partial(jax.jit, donate_argnums=(1,))
            def _prefill_sfx_paged(params, cache, tokens, starts, table,
                                   lens, rows):
                """Suffix prefill (prefix-cache admission): the bucketed
                prefill forward at per-lane START positions — queries
                attend to the zero-copy-bound prefix pages through the
                table, and the row-axis view is GATHERED (prefill_take)
                so a restored ring checkpoint is visible to the call."""
                lanes, T = tokens.shape
                sub = state.prefill_take(cache, rows)
                positions = starts[:, None] + jnp.arange(
                    T, dtype=jnp.int32)[None]
                logits, sub, aux = M.forward(
                    params, cfg, tokens, cache=sub, positions=positions,
                    feature_mode="all", paged=(table, lens),
                    act_spec=act_spec, logits_spec=logits_spec,
                    paged_backend=paged_backend)
                return (logits, state.prefill_merge(cache, sub, rows),
                        aux["features"])

            @functools.partial(jax.jit, donate_argnums=(1,))
            def _fwd_draft_paged(params, cache, tokens, pos, nreal, membed,
                                 table, lens):
                B, T = tokens.shape
                t = jnp.arange(T, dtype=jnp.int32)
                cols = t[None, :] >= nreal[:, None]
                positions = pos[:, None] + t[None]
                ctx = pos[:, None] + jnp.maximum(nreal, 1)[:, None] - 1
                pdraft = {"cols": cols,
                          "ctx": jnp.where(cols, ctx, positions),
                          "sidx": jnp.maximum(t[None, :] - nreal[:, None], 0),
                          "embed": membed}
                logits, cache, aux = M.forward(
                    params, cfg, tokens, cache=cache, positions=positions,
                    feature_mode="all", paged=(table, lens),
                    act_spec=act_spec, logits_spec=logits_spec,
                    paged_backend=paged_backend, pdraft=pdraft)
                return logits, cache, aux["features"][-1]

            self._fwd, self._prefill = _fwd_paged, _prefill_paged
            self._fwd_draft = _fwd_draft_paged
            self._prefill_sfx = _prefill_sfx_paged
            return

        @jax.jit
        def _fwd(params, cache, tokens, pos):
            positions = pos[:, None] + jnp.arange(
                tokens.shape[1], dtype=jnp.int32)[None]
            logits, cache, aux = M.forward(
                params, cfg, tokens, cache=cache, positions=positions,
                feature_mode="all", act_spec=act_spec,
                logits_spec=logits_spec)
            return logits, cache, aux["features"]

        @functools.partial(jax.jit, donate_argnums=(1,))
        def _prefill_dense(params, cache, tokens, rows):
            lanes, T = tokens.shape
            sub = state.prefill_view(cache, lanes)
            positions = jnp.broadcast_to(
                jnp.arange(T, dtype=jnp.int32)[None], (lanes, T))
            logits, sub, aux = M.forward(
                params, cfg, tokens, cache=sub, positions=positions,
                feature_mode="all", act_spec=act_spec,
                logits_spec=logits_spec)
            return (logits, state.prefill_merge(cache, sub, rows),
                    aux["features"])

        @jax.jit
        def _fwd_draft_dense(params, cache, tokens, pos, nreal, membed):
            B, T = tokens.shape
            t = jnp.arange(T, dtype=jnp.int32)
            cols = t[None, :] >= nreal[:, None]
            positions = pos[:, None] + t[None]
            ctx = pos[:, None] + jnp.maximum(nreal, 1)[:, None] - 1
            pdraft = {"cols": cols,
                      "ctx": jnp.where(cols, ctx, positions),
                      "sidx": jnp.maximum(t[None, :] - nreal[:, None], 0),
                      "embed": membed}
            logits, cache, aux = M.forward(
                params, cfg, tokens, cache=cache, positions=positions,
                feature_mode="all", act_spec=act_spec,
                logits_spec=logits_spec, pdraft=pdraft)
            return logits, cache, aux["features"][-1]

        self._fwd, self._prefill = _fwd, _prefill_dense
        self._fwd_draft = _fwd_draft_dense

    # -------------------------------------------------- state delegation
    @property
    def cache(self):
        return self.state.cache

    @cache.setter
    def cache(self, value):
        self.state.cache = value

    @property
    def free_rows(self) -> List[int]:
        return self.state.free_rows

    @property
    def row_pos(self) -> np.ndarray:
        return self.state.row_pos

    @property
    def swappable(self) -> bool:
        return self.state.swappable

    @property
    def swap_dim(self) -> int:
        return self.state.swap_dim

    @property
    def has_ssm(self) -> bool:
        return self.state.has_ssm

    def _fetch(self, arr) -> np.ndarray:
        """The decoder's device -> host gate (swap packing, snapshots)."""
        return _count_fetch(self, arr)

    # ------------------------------------------------------ paged plumbing
    def bind_row(self, row: int, key: Any) -> None:
        """Attach a pool stream to a decoder row (paged backend only):
        every forward reads the row's page table and length live from the
        pool, so pool truncate/adopt are visible with no decoder call."""
        self.state.bind(row, key)

    def unbind_row(self, row: int) -> None:
        self.state.unbind(row)

    def copy_page(self, src: int, dst: int) -> None:
        """Physical COW mirror: duplicate one page in every layer's paged
        buffer (hooked into the pool's cow_listeners by the engine)."""
        self.state.copy_page(src, dst)

    # -------------------------------------------------------------- compute
    @_spanned("engine.decoder_step")
    def step(self, tokens, pos) -> Tuple[jax.Array, jax.Array]:
        """Batched forward: tokens (n_rows, T) int32 (numpy OR device —
        the device-resident loop chains sampled tokens straight back in),
        pos (n_rows,) start positions.  Returns DEVICE (logits
        (n_rows, T, V), feats); nothing crosses to the host."""
        assert tokens.shape[0] == self.n_rows
        if self.paged is not None:
            tab, lens = self.state.table_view()
            logits, self.cache, feats = self._fwd(
                self.params, self.cache, jnp.asarray(tokens, jnp.int32),
                jnp.asarray(pos, jnp.int32), jnp.asarray(tab),
                jnp.asarray(lens))
        else:
            logits, self.cache, feats = self._fwd(
                self.params, self.cache, jnp.asarray(tokens, jnp.int32),
                jnp.asarray(pos, jnp.int32))
        self.n_calls += 1
        self.n_call_tokens += int(np.prod(tokens.shape))
        return logits, feats

    @_spanned("engine.decoder_step")
    def step_draft(self, tokens, pos, nreal, mask_embed
                   ) -> Tuple[jax.Array, jax.Array]:
        """Parallel-draft forward (DESIGN.md §7.12): per row, ``nreal[b]``
        real tokens followed by draft-slot columns (token ids ignored — the
        slot embedding rides there) up to the padded width.  Slot keys are
        stored invisible (dense: position -1; paged: positions >= lens
        route to the trash page) and slot queries see only the row's real
        prefix, so one dispatch yields every slot's hidden state as a
        function of the committed stream alone.  Returns DEVICE (logits
        (n_rows, T, V), last-point features (n_rows, T, D)) for
        ``DL.draft_chunk`` to turn into the multi-head chunk proposal.
        Rows with nreal == 0 (unlisted) are all-slots: every write is
        invisible and their lanes compute garbage the host ignores."""
        assert tokens.shape[0] == self.n_rows
        if self.paged is not None:
            tab, lens = self.state.table_view()
            logits, self.cache, feats = self._fwd_draft(
                self.params, self.cache, jnp.asarray(tokens, jnp.int32),
                jnp.asarray(pos, jnp.int32), jnp.asarray(nreal, jnp.int32),
                mask_embed, jnp.asarray(tab), jnp.asarray(lens))
        else:
            logits, self.cache, feats = self._fwd_draft(
                self.params, self.cache, jnp.asarray(tokens, jnp.int32),
                jnp.asarray(pos, jnp.int32), jnp.asarray(nreal, jnp.int32),
                mask_embed)
        self.n_calls += 1
        self.n_call_tokens += int(np.prod(tokens.shape))
        return logits, feats

    @_spanned("engine.decoder_step")
    def prefill_rows(self, parts: Sequence[Tuple[int, Sequence[int]]]
                     ) -> Tuple[jax.Array, jax.Array]:
        """Batched bucketed prefill: ingest each ``(row, tokens)`` prompt
        into its fresh row with ONE forward at a fixed
        ``(prefill_lanes, ladder-width)`` shape.  Lane i of the returned
        device ``(logits, feats)`` belongs to ``parts[i]``; pad lanes (and
        pad positions beyond a prompt's length) compute garbage that is
        never scattered into a live row — dense/ring lanes carry an
        out-of-bounds row id (dropped by the scatter), paged pad writes
        land in the trash page.

        The ladder quantum bounds pad overshoot to ``quantum - 1``
        positions past a row's logical length, inside the
        ring_slack/ssm_ring margins — the reason prompts ride a quantum
        ladder instead of the power-of-two decode ladder, whose overshoot
        would be unbounded."""
        assert parts and len(parts) <= self.prefill_lanes
        G = self.prefill_lanes
        Tb = DL.prefill_bucket(max(len(t) for _, t in parts),
                               self.prefill_quantum)
        if Tb > self.max_len:
            raise RuntimeError(
                f"prefill bucket {Tb} overflows max_len={self.max_len}")
        toks = np.zeros((G, Tb), np.int32)
        rows = np.full(G, self.n_rows, np.int32)   # OOB lanes scatter-drop
        for i, (row, t) in enumerate(parts):
            L = len(t)
            assert 1 <= L <= Tb
            toks[i, :L] = t
            if L < Tb:
                toks[i, L:] = t[-1]
            rows[i] = row
        if self.paged is not None:
            tab, lens = self.state.table_view(
                [row for row, _ in parts] + [-1] * (G - len(parts)))
            logits, self.cache, feats = self._prefill(
                self.params, self.cache, jnp.asarray(toks),
                jnp.asarray(tab), jnp.asarray(lens), jnp.asarray(rows))
        else:
            logits, self.cache, feats = self._prefill(
                self.params, self.cache, jnp.asarray(toks),
                jnp.asarray(rows))
        for row, t in parts:
            self.state.row_pos[row] = len(t)
        self.n_calls += 1
        self.n_call_tokens += sum(len(t) for _, t in parts)
        self.prefill_shapes.add((G, Tb))
        return logits, feats

    def prefill_row(self, row: int, tokens: Sequence[int]
                    ) -> Tuple[jax.Array, jax.Array]:
        """Singleton ``prefill_rows`` (direct decoder users and tests):
        lane 0 of the returned device (logits, feats) is the row's."""
        return self.prefill_rows([(row, list(tokens))])

    @_spanned("engine.decoder_step")
    def prefill_rows_at(self, parts: Sequence[Tuple[int, Sequence[int]]],
                        starts: Sequence[int]
                        ) -> Tuple[jax.Array, jax.Array]:
        """Bucketed SUFFIX prefill (prefix-cache admission, paged only):
        ``parts[i]`` ingests only the uncached tail of its prompt,
        starting at the page-aligned cached length ``starts[i]`` — its
        row's pool stream must already hold the bound prefix pages plus
        room for the suffix.  The rung width is the SUFFIX length's
        ladder bucket, which is the entire win: a 4-page cached prefix
        never inflates the rung.  Pad-position overshoot past a row's
        logical length is the same < quantum span as ``prefill_rows``
        (trash-paged / future ring slots), so no new margin is needed."""
        assert self.paged is not None, "suffix prefill needs page runs"
        assert parts and len(parts) <= self.prefill_lanes
        assert len(starts) == len(parts)
        G = self.prefill_lanes
        Tb = DL.prefill_bucket(max(len(t) for _, t in parts),
                               self.prefill_quantum)
        if max(starts) + Tb > self.max_len:
            raise RuntimeError(
                f"suffix bucket {Tb} overflows max_len={self.max_len}")
        toks = np.zeros((G, Tb), np.int32)
        rows = np.full(G, self.n_rows, np.int32)   # OOB lanes scatter-drop
        s0 = np.zeros(G, np.int32)
        for i, ((row, t), start) in enumerate(zip(parts, starts)):
            L = len(t)
            assert 1 <= L <= Tb and start >= 0
            toks[i, :L] = t
            if L < Tb:
                toks[i, L:] = t[-1]
            rows[i] = row
            s0[i] = start
        tab, lens = self.state.table_view(
            [row for row, _ in parts] + [-1] * (G - len(parts)))
        logits, self.cache, feats = self._prefill_sfx(
            self.params, self.cache, jnp.asarray(toks), jnp.asarray(s0),
            jnp.asarray(tab), jnp.asarray(lens), jnp.asarray(rows))
        for (row, t), start in zip(parts, starts):
            self.state.row_pos[row] = start + len(t)
        self.n_calls += 1
        self.n_call_tokens += sum(len(t) for _, t in parts)
        self.prefill_shapes.add((G, Tb))
        return logits, feats

    def copy_row(self, src: int, dst: int) -> None:
        """Branch fork: row-axis state (dense KV, SSM rings) copies; paged
        state copies nothing — the fork is page-table sharing in the pool
        (the caller binds dst to the forked stream key)."""
        self.state.fork(src, dst)

    # ----------------------------------------------------------- swap space
    def pack_row(self, row: int, length: int) -> np.ndarray:
        """Flatten the attention half of a row's first ``length`` slots to
        (L, swap_dim) float32 token rows (pos leaves are exact in f32 for
        max_len < 2^24); the flatten/concat runs on device and the result
        crosses the boundary in ONE transfer.  Paged rows are gathered
        page-by-page through the row's table (partial tail page trimmed to
        ``length``) — preemption never densifies the cache.  Recurrent
        ring state is NOT token rows; a hybrid row's ring rides the
        preemption metadata as one explicit ``snapshot``."""
        return self._fetch(self.state.pack_row(row, length))

    def unpack_row(self, row: int, rows: np.ndarray) -> None:
        """Restore a row from packed token-rows (inverse of pack_row);
        dense slots beyond len(rows) are reset to empty (pos = -1), paged
        rows scatter straight into the freshly re-extended table."""
        self.state.unpack_row(row, rows)

    # ---------------------------------------------------- SSM checkpoints
    def snapshot(self, row: int, step: int) -> List[Dict[str, np.ndarray]]:
        """Host copy of one row's recurrent state at stream length
        ``step`` (one {h, conv} dict per mamba slot), flattened on device
        and fetched in ONE transfer.  The serving engines use this as the
        ring's swap side-channel (paged preemption) and the property tests
        use it to pin ring contents; ordinary rollback never needs it —
        every forward restores implicitly through its start position."""
        buf = self._fetch(self.state.snapshot_flat(row, step))
        return self.state.snapshot_split(buf)

    def restore(self, row: int, step: int,
                snap: List[Dict[str, np.ndarray]]) -> None:
        """Write a ``snapshot`` back into the ring at ``step`` — after
        which a forward starting at position ``step`` resumes from it."""
        self.state.restore(row, step, snap)


# ---------------------------------------------------------------------------
# per-request state
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Stream:
    """One model-side token stream living in a decoder row."""
    row: int
    ing: int = 0                     # KV slots written (row positions 0..)
    pending: List[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _Seq:
    rid: int
    prompt: List[int]
    max_new: int
    on_token: Optional[Callable[[int, int, float], None]]
    ctr: int = 0                     # PRNG decision counter (folded key)
    tgt: _Stream = None
    dft: _Stream = None
    out: List[int] = dataclasses.field(default_factory=list)
    stats: GenStats = dataclasses.field(default_factory=GenStats)
    streamed: int = 0                # tokens already delivered via callback
    admit_order: int = -1
    done: bool = False
    feats_last: Optional[jax.Array] = None   # (n_points, 1, D)
    # SpecBranch carried state — distributions stay on device
    mode: str = "draft"
    chunk: List[int] = dataclasses.field(default_factory=list)
    chunk_q: List[jax.Array] = dataclasses.field(default_factory=list)
    q_b: Optional[jax.Array] = None          # (V,) signal LOGITS, device
    q_b_conf: float = 0.0                    # host copy of max signal prob
    # this round's history-predictor decision (runtime/predictor.py);
    # None whenever the predictor is off
    pdec: Optional[Any] = None
    # prefix-cache publish candidate (set at admission, prefix_cache only):
    # the page-aligned prefill-written prompt prefix this request may hand
    # to the cache at retire/preempt, plus the ring snapshot recorded at
    # that length for SSM-bearing decoders
    pub_len: int = 0
    pub_snaps: Optional[Dict[str, Any]] = None

    @property
    def committed(self) -> int:
        """Committed stream length = prompt + generated."""
        return len(self.prompt) + len(self.out)


# ---------------------------------------------------------------------------
# engine base
# ---------------------------------------------------------------------------

class BatchedEngineBase:
    name = "batched-base"
    draft_rows_per_seq = 1

    def __init__(self, draft_params, draft_cfg: ModelConfig,
                 target_params, target_cfg: ModelConfig,
                 ecfg: EngineConfig, *,
                 max_batch: int = 8,
                 page_size: int = 16,
                 pool_pages: Optional[int] = None,
                 swap_pages: int = 0,
                 hrad_params=None,
                 draft_heads=None,
                 attn_backend: str = "dense",
                 prefix_cache: bool = False,
                 debug_check: bool = False,
                 mesh=None):
        assert attn_backend in ("dense", "paged"), attn_backend
        if prefix_cache and attn_backend != "paged":
            raise ValueError(
                "prefix_cache=True requires attn_backend='paged': dense "
                "rows have no page runs to share — drop prefix_cache or "
                "switch to the paged backend")
        self.dp, self.dcfg = draft_params, draft_cfg
        self.tp, self.tcfg = target_params, target_cfg
        self.ecfg = ecfg
        self.hrad_params = hrad_params
        # single-pass parallel drafting (DESIGN.md §7.12): multi-position
        # draft heads collapse the per-round draft phase to ONE dispatch.
        self.draft_heads = draft_heads
        if ecfg.draft_mode not in ("sequential", "parallel"):
            raise ValueError(f"unknown draft_mode {ecfg.draft_mode!r}")
        if ecfg.draft_mode == "parallel":
            if draft_heads is None:
                raise ValueError(
                    "draft_mode='parallel' needs draft_heads "
                    "(models.init_draft_heads / training.pairs)")
            if any(m == "mamba" for m, _ in draft_cfg.pattern):
                raise ValueError(
                    "parallel drafting needs an attention-only draft model; "
                    f"pattern has mamba mixers: {draft_cfg.pattern}")
            need = max(ecfg.gamma, ecfg.gamma_branch)
            have = int(draft_heads["heads"].shape[0])
            if have < need:
                raise ValueError(
                    f"draft_heads has {have} positions; "
                    f"need >= max(gamma, gamma_branch) = {need}")
        self.max_batch = max_batch
        self.attn_backend = attn_backend
        self.debug_check = debug_check
        # serving mesh (DESIGN.md §7.10): both decoders shard
        # tensor-parallel over its "model" axis and the device-loop
        # functions pin their host packets replicated over it; mesh=None
        # is today's single-device path, bit-for-bit.
        self.mesh = mesh
        # device-resident loop constants (DESIGN.md §7.7)
        self._key = jax.random.PRNGKey(ecfg.seed & 0x7FFFFFFF)
        self._tt = float(ecfg.temperature)
        self._dt = float(ecfg.draft_temperature)
        self._st = float(ecfg.signal_temperature)
        # chunk pad width: a carried chunk is a serial draft (<= gamma) OR
        # an adopted branch continuation (<= gamma_branch)
        self._CH = DL.bucket(max(1, ecfg.gamma, ecfg.gamma_branch))
        # history-driven speculation controller (runtime/predictor.py):
        # None when spec_predictor == "off", and every predictor branch in
        # the round loops is guarded on that — the off path stays bitwise-
        # identical to the predictor-less build.  Adjusted gammas stay on
        # the bucket ladder <= ecfg.gamma, so _CH, the admission headroom
        # and the jit trace set all keep their static bounds.
        self.predictor = PRED.make_predictor(
            ecfg.spec_predictor, ecfg.gamma, ecfg.k_max, ecfg.epsilon)
        self._K = max(1, ecfg.k_max)
        # fused verify route: the batched Pallas verify_accept kernel on
        # TPU (pre-scaled logits), the compiled XLA twin elsewhere
        self._use_kernel = DL.kernel_route(self._tt, self._dt)
        self._kernel_interpret = default_interpret()
        # uniform-window width one branch verify consumes per request:
        # [0, CH] chain block + [CH+1, CH+1+K] branch block
        self._W = self._CH + 1 + self._K + 1
        self.xfer_bytes = 0
        self.xfer_fetches = 0
        # split page-id spaces (DESIGN.md §7.6): target streams ("t", rid)
        # and draft streams ("d"/"b", ...) allocate from separate pools, so
        # each physically paged decoder sizes its buffers to ITS pages only
        # (the PR 2 shared id space made every buffer pool-wide, ~2x).
        if pool_pages is None:
            t_pages = -(-max_batch * ecfg.max_len // page_size)
            d_pages = -(-max_batch * self.draft_rows_per_seq
                        * ecfg.max_len // page_size)
        else:
            # explicit total (tests/CLI): split by worst-case stream count
            per_seq = 1 + self.draft_rows_per_seq
            t_pages = max(2, round(pool_pages / per_seq))
            d_pages = max(2, pool_pages - t_pages)
        self.pools: Dict[str, PagedKVPool] = {
            "t": PagedKVPool(t_pages, page_size),
            "d": PagedKVPool(d_pages, page_size),
        }
        self.pool = PoolGroup(self.pools)      # aggregate metrics view
        # prefill length-ladder quantum: admission groups pad prompts up
        # to multiples of this, so the pad span (< quantum) must fit the
        # same ring/slack margins that cover decode-bucket overshoot.
        self._pq = 8
        # ring deep enough for one worst-case round of forward progress
        # (pending + chunk + branch continuation + batch-pad margin,
        # including bucket-ladder overshoot AND prefill-ladder padding)
        # PLUS the rollback span back across it, with slack; ~KBs per row.
        ssm_ring = (4 * (ecfg.gamma + ecfg.gamma_branch)
                    + 2 * DL.bucket(ecfg.gamma + 2) + 16 + self._pq)
        if ecfg.draft_mode == "parallel":
            # parallel rounds re-ingest the committed tail after a reject
            # (pending = full[ing:]) and stage slot columns past it; widen
            # the ring ONLY in this mode — ring size changes the float
            # summation order, and sequential mode is pinned bitwise.
            ssm_ring += 2 * DL.bucket(2 * (ecfg.gamma + ecfg.gamma_branch)
                                      + 4)
        paged = attn_backend == "paged"
        lanes = DL.bucket(max_batch)   # admission groups are <= max_batch
        self.tgt_dec = BatchedDecoder(target_params, target_cfg,
                                      n_rows=max_batch, max_len=ecfg.max_len,
                                      paged=self.pools["t"] if paged else None,
                                      ssm_ring=ssm_ring,
                                      prefill_lanes=lanes,
                                      prefill_quantum=self._pq, mesh=mesh)
        self.dft_dec = BatchedDecoder(draft_params, draft_cfg,
                                      n_rows=max_batch
                                      * self.draft_rows_per_seq,
                                      max_len=ecfg.max_len,
                                      paged=self.pools["d"] if paged else None,
                                      ssm_ring=ssm_ring,
                                      prefill_lanes=lanes,
                                      prefill_quantum=self._pq, mesh=mesh)
        if paged:
            # accounting COW (pool) -> physical COW, each in its own buffer
            self.pools["t"].cow_listeners.append(self.tgt_dec.copy_page)
            self.pools["d"].cow_listeners.append(self.dft_dec.copy_page)
        # cross-request radix prefix cache (DESIGN.md §7.13): None (the
        # default) keeps every admission/retire path bitwise today's —
        # no lookups, no publishes, no extra snapshots or fetches.
        self.prefix_cache: Optional[PrefixCache] = \
            PrefixCache(self.pools) if prefix_cache else None
        self.swap: Optional[PagedStore] = None
        if swap_pages > 0 and self.tgt_dec.swappable:
            self.swap = PagedStore(swap_pages, page_size,
                                   self.tgt_dec.swap_dim)
        self._swapped: Dict[int, dict] = {}      # rid -> swap metadata
        self._pending_admits: List[Tuple[_Seq, List[int], bool, int]] = []
        self.cost = CostModel(c=ecfg.c)
        self.clock = 0.0
        self.timeline: List[Tuple[str, int, int]] = []
        self.active: List[_Seq] = []
        self._admit_counter = 0
        # observability (obs/trace.py): NULL_RECORDER keeps every hook a
        # no-op; every event an enabled recorder sees is built from values
        # already host-resident, so tracing adds zero device syncs.
        self.rec = NULL_RECORDER

    def set_recorder(self, rec) -> None:
        """Install a trace recorder.  An enabled recorder additionally taps
        the page pools' reclaim and COW listeners for per-cause/per-pool
        attribution (both fire on host accounting already in flight — zero
        extra device syncs)."""
        self.rec = rec
        if rec.enabled:
            for which, pool in self.pools.items():
                pool.reclaim_listeners.append(
                    functools.partial(self._on_reclaim, which))
                pool.cow_listeners.append(
                    functools.partial(self._on_cow, which))

    def _on_cow(self, which: str, old: int, new: int) -> None:
        self.rec.cow(which)

    def _on_reclaim(self, which: str, reason: str, freed: int) -> None:
        self.rec.reclaim(which, reason, freed)

    def _pool_of(self, key: Any) -> PagedKVPool:
        """Route a stream key to its id space: target streams ("t", rid)
        live in the target pool; draft streams and their branch forks
        ("d", rid) / ("b", rid, i) in the draft pool."""
        return self.pools["t" if key[0] == "t" else "d"]

    # ------------------------------------------------------- host boundary
    def _fetch(self, arr) -> np.ndarray:
        """The engines' device -> host gate: small packets (tokens,
        confidences, verdicts) — never logits."""
        return _count_fetch(self, arr)

    def _count_staged(self, nbytes: int) -> None:
        """Admission-side host boundary crossings (prefill token frames,
        swap readback, ring restore) — tallied on the ENGINE so the
        decoders' fetch counters keep meaning 'device -> host packet
        fetches' (tests pin that)."""
        self.xfer_bytes += int(nbytes)
        self.xfer_fetches += 1

    @property
    def host_transfer_bytes(self) -> int:
        """Total bytes this engine has moved across the host boundary:
        device -> host packets, swap packing and ring snapshots (PR 4's
        decode-loop tally) plus admission traffic — prefill token-frame
        staging, swap readback and ring restore."""
        return (self.xfer_bytes + self.tgt_dec.xfer_bytes
                + self.dft_dec.xfer_bytes)

    @property
    def host_fetches(self) -> int:
        return (self.xfer_fetches + self.tgt_dec.xfer_fetches
                + self.dft_dec.xfer_fetches)

    # ------------------------------------------------------------ H-RAD
    def _embed_of(self, token: int) -> jax.Array:
        return self.tp["embed"][jnp.asarray([token])].astype(jnp.float32)

    def _hrad_signal(self, seq: _Seq, token: int) -> int:
        if (not self.ecfg.use_hrad or self.hrad_params is None
                or seq.feats_last is None):
            return 1
        z = H.build_feature(seq.feats_last, self._embed_of(token),
                            self.ecfg.hrad_k_layers)
        s = int(self._fetch(H.predict(self.hrad_params, z))[0])
        seq.stats.hrad_signals.append(s)
        return s

    # ---------------------------------------------------------- batched fwd
    def _batched(self, dec: BatchedDecoder,
                 parts: List[Tuple[int, List[int], int]]
                 ) -> Tuple[jax.Array, jax.Array]:
        """One batched forward with host-staged tokens.  parts: (row,
        real_tokens, start_pos).  The token width is padded up the bucket
        ladder so ragged chunk lengths hit a handful of compiled shapes.
        Rows not listed tick in place at their own write head: their pad
        writes land on the slot their next real write will overwrite, and
        stay causally masked until then.  Returns DEVICE (logits
        (B, T, V), feats)."""
        T = DL.bucket(max(len(t) for _, t, _ in parts))
        toks = np.zeros((dec.n_rows, T), np.int32)
        pos = np.minimum(dec.row_pos, dec.max_len - T).astype(np.int32)
        # ^ free rows only: live rows are guaranteed max_len headroom at
        #   admission (can_admit), so the clamp never moves a live head
        for row, t, p0 in parts:
            if p0 + T > dec.max_len:
                raise RuntimeError(
                    f"row {row} overflows max_len={dec.max_len}")
            toks[row, :len(t)] = t
            if len(t) < T:
                toks[row, len(t):] = t[-1]
            pos[row] = p0
        logits, feats = dec.step(toks, pos)
        for row, t, p0 in parts:
            dec.row_pos[row] = p0 + len(t)
        return logits, feats

    def _ingest(self, dec: BatchedDecoder,
                triples: List[Tuple[_Stream, Any, List[int]]]
                ) -> Tuple[jax.Array, jax.Array]:
        """Batched ingest of per-stream token lists + pool accounting."""
        for st, pool_key, toks in triples:
            self._pool_of(pool_key).extend(pool_key, len(toks))
        parts = [(st.row, toks, st.ing) for st, _, toks in triples]
        out = self._batched(dec, parts)
        for st, _, toks in triples:
            st.ing += len(toks)
        return out

    def _ingest_dev(self, dec: BatchedDecoder,
                    pairs: List[Tuple[_Stream, Any]],
                    tokens_by_row: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Single-token batched ingest with DEVICE tokens: each listed
        stream consumes tokens_by_row[stream.row] straight from the
        previous tick's sample — the sampled ids never visit the host.
        Unlisted rows park at their write head (token id 0, causally
        masked)."""
        mask = np.zeros(dec.n_rows, bool)
        pos = np.minimum(dec.row_pos, dec.max_len - 1).astype(np.int32)
        for st, pool_key in pairs:
            self._pool_of(pool_key).extend(pool_key, 1)
            if st.ing + 1 > dec.max_len:
                raise RuntimeError(
                    f"row {st.row} overflows max_len={dec.max_len}")
            mask[st.row] = True
            pos[st.row] = st.ing
        col = DL.masked_token_column(tokens_by_row, jnp.asarray(mask))
        logits, feats = dec.step(col, pos)
        for st, _ in pairs:
            st.ing += 1
            dec.row_pos[st.row] = st.ing
        return logits, feats

    # ----------------------------------------------------------- admission
    def _pool_keys(self, rid: int) -> Tuple[Any, Any]:
        return ("t", rid), ("d", rid)

    def admit_cost_pages(self, prompt_len: int) -> int:
        """Pages an admission takes from EACH pool (one prompt-length
        stream per id space)."""
        return self.pools["t"].pages_for(prompt_len - 1)

    def _max_len_headroom(self) -> int:
        """Worst-case tokens a live row can hold beyond prompt + max_new:
        one round of overshoot (chunk/bonus) plus a branch continuation
        plus bucket-ladder and batch-pad margin — rows must never come
        within a batched call's padding of max_len (see _batched)."""
        extra = 0
        if self.ecfg.draft_mode == "parallel":
            # a parallel-draft frame stages the re-ingested committed tail
            # plus G slot columns in one bucketed call
            extra = DL.bucket(2 * (self.ecfg.gamma
                                   + self.ecfg.gamma_branch) + 4)
        return (2 * (DL.bucket(self.ecfg.gamma + 2)
                     + DL.bucket(self.ecfg.gamma_branch + 2) + 4)
                + self._pq           # prefill-ladder pad span
                + extra)

    def can_admit(self, prompt_len: int, max_new: int = 0) -> bool:
        if not self.tgt_dec.free_rows or len(self.active) >= self.max_batch:
            return False
        if len(self.dft_dec.free_rows) < self.draft_rows_per_seq:
            return False
        if (prompt_len + max_new + self._max_len_headroom()
                > self.ecfg.max_len):
            return False
        need = self.admit_cost_pages(prompt_len)
        # pages held only by prefix-cache runs count as free headroom:
        # reserve realizes them through LRU eviction on demand (and a
        # cache HIT shrinks the bind-side need by exactly the pages it
        # pins, so the arithmetic holds hit or miss)
        pc = self.prefix_cache
        return all(need + self._round_slack_pages(which)
                   <= pool.free_pages
                   + (pc.reclaimable(which) if pc is not None else 0)
                   for which, pool in self.pools.items())

    def _round_slack_pages(self, which: str) -> int:
        """Pages one request may need from pool ``which`` for one
        worst-case round — kept free at admission so a fresh admit cannot
        immediately force preemption."""
        g, gb = self.ecfg.gamma, self.ecfg.gamma_branch
        if which == "t":
            return self.pools["t"].pages_for(2 + g)
        worst = g + 1
        if self.draft_rows_per_seq > 1:
            worst += (self.draft_rows_per_seq - 1) * (1 + gb)
        return self.pools["d"].pages_for(worst) + self.draft_rows_per_seq

    def resume_out_len(self, rid: int) -> int:
        """Tokens already generated by a parked (preempted) request — they
        re-enter the prompt at re-admission."""
        meta = self._swapped.get(rid)
        return len(meta["seq"].out) if meta is not None else 0

    def reserve(self, rid: int, prompt: Sequence[int], max_new: int,
                on_token=None) -> _Seq:
        """Admission bookkeeping for one request (rows, pool streams, swap
        restore) with the prefill forward DEFERRED: the scheduler reserves
        a whole admission group, then ``commit_admissions`` ingests it with
        one batched bucketed prefill per (decoder, ladder rung) instead of
        one batch-1 forward per request."""
        meta = self._swapped.pop(rid, None)
        if meta is not None:
            seq = meta["seq"]
        else:
            seq = _Seq(rid=rid, prompt=list(prompt), max_new=max_new,
                       on_token=on_token)
        toks = seq.prompt + seq.out
        assert len(toks) >= 2, "need a prompt of >= 2 tokens"
        L = len(toks) - 1
        tk, dk = self._pool_keys(rid)
        pc = self.prefix_cache
        # ---- prefix-cache lookup (DESIGN.md §7.13): longest cached
        # page-aligned prefix of the prompt, capped so >= 1 suffix token
        # remains to prefill (feats_last and the pending seed need it).
        # Swap re-admissions keep the unpack path: unpack scatters into
        # EVERY page of a full-length stream, which must not be shared.
        ent, hit = None, 0
        if pc is not None and (meta is None
                               or meta.get("swap_key") is None):
            need_snaps = self.tgt_dec.has_ssm or self.dft_dec.has_ssm
            found = pc.lookup(toks, L - 1, need_snaps=need_snaps)
            if found is not None:
                ent, hit = found
            if self.rec.enabled:
                self.rec.prefix("hit" if hit else "miss", rid=rid,
                                tokens=hit, prompt_len=len(toks),
                                t=self.clock)
        if hit:
            # zero-copy bind: the request's streams share the run's pages
            # (refcount bump) — the exact branch-fork COW contract, so a
            # later tail-page append splits before writing.
            self.pools["t"].fork_prefix(ent.stream, tk, hit)
            self.pools["d"].fork_prefix(ent.stream, dk, hit)
        else:
            self.pools["t"].open(tk)
            self.pools["d"].open(dk)
        try:
            for which, key in (("t", tk), ("d", dk)):
                while True:
                    try:
                        self.pools[which].extend(key, L - hit)
                        break
                    except PoolExhausted:
                        # realize the headroom can_admit counted: evict
                        # LRU cache runs (the just-bound run is pinned by
                        # the live refs above) until the suffix fits
                        if pc is None or not pc.evict_lru():
                            raise
                        if self.rec.enabled:
                            self.rec.prefix("evict", t=self.clock)
        except PoolExhausted:
            self.pools["t"].close(tk, "preempt")
            self.pools["d"].close(dk, "preempt")
            if meta is not None:
                self._swapped[rid] = meta
            raise
        t_row = self.tgt_dec.free_rows.pop()
        d_row = self.dft_dec.free_rows.pop()
        self.tgt_dec.bind_row(t_row, tk)
        self.dft_dec.bind_row(d_row, dk)
        if hit and ent.snaps:
            # SSM half of the hit: restore the ring snapshot recorded at
            # the shared length, after which the suffix forward starting
            # at position ``hit`` resumes from it (same side-channel as
            # preemption swap).
            for which, dec, row in (("t", self.tgt_dec, t_row),
                                    ("d", self.dft_dec, d_row)):
                snap = ent.snaps.get(which)
                if snap is not None and dec.has_ssm:
                    dec.restore(row, hit, snap)
                    self._count_staged(sum(a.nbytes for s in snap
                                           for a in s.values()))
        restored = False
        if meta is not None and meta.get("swap_key") is not None:
            rows = self.swap.get(meta["swap_key"])
            self._count_staged(rows.nbytes)
            self.tgt_dec.unpack_row(t_row, rows)
            if meta.get("ssm_snap") is not None:
                # the ring's swap side-channel: recurrent state is not
                # token rows — restore the packed-length checkpoint the
                # preemption snapshotted (DESIGN.md §7.8)
                self.tgt_dec.restore(t_row, L, meta["ssm_snap"])
                self._count_staged(sum(a.nbytes for d in meta["ssm_snap"]
                                       for a in d.values()))
            self.swap.drop(meta["swap_key"])
            seq.feats_last = meta["feats_last"]
            restored = True
        seq.tgt = _Stream(row=t_row, ing=L, pending=[toks[-1]])
        seq.dft = _Stream(row=d_row, ing=L, pending=[toks[-1]])
        seq.mode, seq.chunk, seq.chunk_q, seq.q_b = "draft", [], [], None
        if self.predictor is not None:
            # keyed by rid: acceptance history survives preemption and
            # re-admission (start is idempotent)
            self.predictor.start(rid)
        seq.admit_order = self._admit_counter
        self._admit_counter += 1
        self.active.append(seq)
        self._pending_admits.append((seq, toks[:-1], restored, hit))
        if self.rec.enabled:
            self.rec.request("admit", rid, prompt_len=len(toks),
                             restored=restored, t=self.clock)
            if restored:
                self.rec.request("swap_in", rid, t=self.clock)
        return seq

    @_spanned("engine.admit")
    def commit_admissions(self) -> None:
        """Run the deferred prefills of the current admission group: group
        prompts onto the prefill length ladder and ingest each rung with
        ONE forward per decoder (swap-restored target rows skip theirs).
        One admission round therefore costs one forward per distinct
        bucket, not one per request — and one compiled trace per bucket,
        not one per distinct prompt length."""
        pending, self._pending_admits = self._pending_admits, []
        if not pending:
            return
        # bucket by the UNCACHED suffix length: a prefix-cache hit rides a
        # rung sized to its suffix, never its full prompt — that is the
        # admission win.  Misses (hit == 0) bucket by full length exactly
        # as before, so the cache-off path is bitwise today's.
        buckets: Dict[int, List[Tuple[_Seq, List[int], bool, int]]] = {}
        for seq, toks, restored, hit in pending:
            width = DL.prefill_bucket(len(toks) - hit, self._pq)
            buckets.setdefault(width, []).append((seq, toks, restored, hit))
        lanes = self.tgt_dec.prefill_lanes
        n_fwd, staged_tokens = 0, 0
        for width in sorted(buckets):
            grp = buckets[width]
            for i in range(0, len(grp), lanes):
                chunk = grp[i:i + lanes]
                tparts = [(seq.tgt.row, toks)
                          for seq, toks, restored, hit in chunk
                          if not restored and not hit]
                if tparts:
                    _, feats = self.tgt_dec.prefill_rows(tparts)
                    # the staged (lanes, width) int32 token frame crosses
                    # host -> device once per prefill forward
                    self._count_staged(lanes * width * 4)
                    n_fwd += 1
                    staged_tokens += lanes * width
                    lane = 0
                    for seq, toks, restored, hit in chunk:
                        if restored or hit:
                            continue
                        seq.feats_last = feats[:, lane:lane + 1,
                                               len(toks) - 1, :]
                        seq.stats.target_calls += 1   # restores skip this
                        lane += 1
                    if self.rec.enabled:
                        self.rec.prefill(
                            width=width, lanes=lanes, used=len(tparts),
                            tokens=sum(len(t) for _, t in tparts),
                            t=self.clock)
                hgrp = [(seq, toks, hit)
                        for seq, toks, restored, hit in chunk if hit]
                if hgrp:
                    # suffix prefill over the zero-copy-bound prefix pages
                    _, feats = self.tgt_dec.prefill_rows_at(
                        [(seq.tgt.row, toks[hit:]) for seq, toks, hit
                         in hgrp],
                        [hit for _, _, hit in hgrp])
                    self._count_staged(lanes * width * 4)
                    n_fwd += 1
                    staged_tokens += lanes * width
                    for lane, (seq, toks, hit) in enumerate(hgrp):
                        seq.feats_last = feats[:, lane:lane + 1,
                                               len(toks) - hit - 1, :]
                        seq.stats.target_calls += 1
                    if self.rec.enabled:
                        self.rec.prefill(
                            width=width, lanes=lanes, used=len(hgrp),
                            tokens=sum(len(t) - h for _, t, h in hgrp),
                            t=self.clock)
                dparts = [(seq.dft.row, toks)
                          for seq, toks, _, hit in chunk if not hit]
                if dparts:
                    self.dft_dec.prefill_rows(dparts)
                    self._count_staged(lanes * width * 4)
                    n_fwd += 1
                    staged_tokens += lanes * width
                    if self.rec.enabled:
                        self.rec.prefill(
                            width=width, lanes=lanes, used=len(dparts),
                            tokens=sum(len(t) for _, t in dparts),
                            t=self.clock)
                if hgrp:
                    self.dft_dec.prefill_rows_at(
                        [(seq.dft.row, toks[hit:]) for seq, toks, hit
                         in hgrp],
                        [hit for _, _, hit in hgrp])
                    self._count_staged(lanes * width * 4)
                    n_fwd += 1
                    staged_tokens += lanes * width
                    if self.rec.enabled:
                        self.rec.prefill(
                            width=width, lanes=lanes, used=len(hgrp),
                            tokens=sum(len(t) - h for _, t, h in hgrp),
                            t=self.clock)
        if self.prefix_cache is not None:
            self._capture_publish_candidates(pending)
        # admission pricing (runtime/cost_model.py): with t_prefill left at
        # its 0 default no round is appended and the clock never moves —
        # bitwise today's TTFT.  Priced, a cached admission's smaller rungs
        # and fewer forwards cut modeled TTFT, which is what the prefix-
        # cache bench gates on.
        if self.cost.t_prefill > 0.0 and n_fwd:
            rnd = ("prefill", staged_tokens, n_fwd)
            self.timeline.append(rnd)
            self.clock += self.cost.round_cost(rnd)
        if self.debug_check:
            self.pool.check()
            if self.prefix_cache is not None:
                self.prefix_cache.check()

    def _capture_publish_candidates(
            self, pending: List[Tuple[_Seq, List[int], bool, int]]) -> None:
        """Record what each fresh admission may hand to the prefix cache
        at retire/preempt: its page-aligned prefill-written prompt prefix
        plus — for SSM-bearing decoders — the ring snapshot at exactly
        that length.  The snapshot must be taken NOW: the prefill just
        wrote checkpoints ``hit+1..L`` and the publish length is within a
        page of L, so the slot is live; by retire time the decode loop's
        ring writes could have lapped it.  Swap-restored re-admissions
        skip their prefill, so they keep the candidate captured at their
        original admission (pack/unpack is bitwise)."""
        ps = self.pool.page_size
        for seq, toks, restored, hit in pending:
            if restored:
                continue
            seq.pub_len = (len(toks) // ps) * ps
            seq.pub_snaps = None
            if not seq.pub_len:
                continue
            snaps: Dict[str, Any] = {}
            for which, dec, st in (("t", self.tgt_dec, seq.tgt),
                                   ("d", self.dft_dec, seq.dft)):
                if dec.has_ssm:
                    snaps[which] = dec.snapshot(st.row, seq.pub_len)
            seq.pub_snaps = snaps or None

    def admit(self, rid: int, prompt: Sequence[int], max_new: int,
              on_token=None) -> _Seq:
        """Admit (or re-admit after preemption) one request immediately —
        a singleton admission group."""
        seq = self.reserve(rid, prompt, max_new, on_token=on_token)
        self.commit_admissions()
        return seq

    # ----------------------------------------------------------- preemption
    def preempt_youngest(self) -> _Seq:
        """Evict the most recently admitted request (FIFO-preserving) and
        release its rows and pages.  Target KV is parked in the paged swap
        store when possible; otherwise the prefix is recomputed at
        re-admission."""
        victim = max(self.active, key=lambda s: s.admit_order)
        self.active.remove(victim)
        meta = {"seq": victim, "swap_key": None, "ssm_snap": None,
                "feats_last": victim.feats_last}
        if self.swap is not None and victim.tgt.ing > 0:
            key = ("swap", victim.rid, victim.admit_order)
            try:
                self.swap.put(key, self.tgt_dec.pack_row(victim.tgt.row,
                                                         victim.tgt.ing))
                meta["swap_key"] = key
                if self.tgt_dec.has_ssm:
                    # recurrent state rides the metadata as one explicit
                    # checkpoint at the packed length (paged hybrid swap)
                    meta["ssm_snap"] = self.tgt_dec.snapshot(
                        victim.tgt.row, victim.tgt.ing)
            except PoolExhausted:
                pass
        self._publish_prefix(victim)
        tk, dk = self._pool_keys(victim.rid)
        self.pools["t"].close(tk, "preempt")
        self.pools["d"].close(dk, "preempt")
        self.tgt_dec.unbind_row(victim.tgt.row)
        self.dft_dec.unbind_row(victim.dft.row)
        self.tgt_dec.free_rows.append(victim.tgt.row)
        self.dft_dec.free_rows.append(victim.dft.row)
        victim.tgt = victim.dft = None
        victim.mode, victim.chunk, victim.chunk_q = "draft", [], []
        victim.q_b = None
        self._swapped[victim.rid] = meta
        if self.rec.enabled:
            self.rec.request("preempt", victim.rid, t=self.clock,
                             swapped=meta["swap_key"] is not None)
            if meta["swap_key"] is not None:
                self.rec.request("swap_out", victim.rid, t=self.clock)
        return victim

    def _make_room(self, seqs: List[_Seq],
                   fits: Callable[[List[_Seq]], bool]) -> List[_Seq]:
        """Preempt youngest-first until this round's worst case fits —
        but spill the prefix cache first: LRU runs no live request holds
        are strictly cheaper to give up than a live request's rows."""
        preempted = []
        while not fits(seqs):
            if (self.prefix_cache is not None
                    and self.prefix_cache.evict_lru()):
                if self.rec.enabled:
                    self.rec.prefix("evict", t=self.clock)
                continue
            if len(seqs) <= 1:
                raise RuntimeError(
                    "KV pool too small to run a single request round "
                    f"({self.pool.num_pages} pages x {self.pool.page_size})")
            victim = self.preempt_youngest()
            seqs.remove(victim)
            preempted.append(victim)
        return preempted

    # ------------------------------------------------------------- commits
    def _commit(self, seq: _Seq, tokens: List[int], now: float) -> None:
        seq.out.extend(tokens)
        seq.stats.emitted += len(tokens)
        if seq.on_token is not None:
            while seq.streamed < min(len(seq.out), seq.max_new):
                seq.on_token(seq.rid, seq.out[seq.streamed], now)
                seq.streamed += 1
        if len(seq.out) >= seq.max_new:
            seq.done = True

    def _rollback_streams(self, seq: _Seq) -> None:
        """Reset both streams to the committed prefix, newest token pending
        (the engines' uniform lineage reset), reclaiming rejected pages."""
        keep = seq.committed - 1
        tk, dk = self._pool_keys(seq.rid)
        for st, key, dec in ((seq.tgt, tk, self.tgt_dec),
                             (seq.dft, dk, self.dft_dec)):
            if st.ing > keep:
                self._pool_of(key).truncate(key, keep, "rollback")
            st.ing = min(st.ing, keep)
            # a positional reset never needs replay: attention masks stale
            # slots causally, SSM rings resume from the keep-checkpoint.
            # The write head must follow the reset: idle-row pad writes park
            # at row_pos, and a stale head would park junk at a slot a
            # local-attention ring still needs (evicting a key inside other
            # queries' windows) instead of the slot the next real write
            # overwrites anyway.
            dec.row_pos[st.row] = st.ing
            # pending = the committed tail past the kept prefix.  In
            # sequential mode ing == keep always holds here (every round
            # ingests pending before drafting), so this reduces bitwise to
            # the historical [seq.out[-1]].  In parallel mode the draft
            # stream only ever holds the committed prefix (drafted tokens
            # never enter its cache), so after a reject its tail can span
            # several committed tokens.
            full = seq.prompt + seq.out
            st.pending = [int(t) for t in full[st.ing:]]

    # ----------------------------------------------------- prefix publish
    def _publish_prefix(self, seq: _Seq) -> None:
        """Hand the request's prefill-written prompt prefix to the prefix
        cache — a zero-copy refcount bump on its first ``pub_len`` tokens'
        pages in BOTH pools.  Must run before the streams close at
        retire/preempt so the run survives the release; safe because the
        engines never truncate below committed-1 >= pub_len and never
        write a slot below the stream length (a tail-page append onto the
        now-shared last page goes through the pool's COW split)."""
        pc = self.prefix_cache
        if pc is None or seq.pub_len <= 0:
            return
        tk, dk = self._pool_keys(seq.rid)
        created = pc.publish(seq.prompt + seq.out, seq.pub_len,
                             {"t": tk, "d": dk}, snaps=seq.pub_snaps)
        if self.rec.enabled:
            self.rec.prefix("publish", rid=seq.rid, tokens=seq.pub_len,
                            created=created, t=self.clock)

    # -------------------------------------------------------------- retire
    @_spanned("engine.retire")
    def retire_done(self) -> List[Tuple[_Seq, GenResult]]:
        out = []
        for seq in [s for s in self.active if s.done]:
            self.active.remove(seq)
            self._publish_prefix(seq)
            tk, dk = self._pool_keys(seq.rid)
            self.pools["t"].close(tk, "retire")
            self.pools["d"].close(dk, "retire")
            self.tgt_dec.unbind_row(seq.tgt.row)
            self.dft_dec.unbind_row(seq.dft.row)
            self.tgt_dec.free_rows.append(seq.tgt.row)
            self.dft_dec.free_rows.append(seq.dft.row)
            if self.predictor is not None:
                self.predictor.drop(seq.rid)
            seq.stats.finish()
            if self.rec.enabled:
                self.rec.finish(seq.rid, emitted=seq.stats.emitted,
                                rollback_tokens=seq.stats.rollback_tokens,
                                pruned_tokens=seq.stats.pruned_tokens,
                                t=self.clock)
            out.append((seq, GenResult(seq.out[:seq.max_new], seq.stats,
                                       [])))
        if self.debug_check:
            self.pool.check()
        return out

    # --------------------------------------------------------------- round
    def step_round(self) -> Dict[str, Any]:
        raise NotImplementedError

    def _finish_round(self, kind: str, draft_steps: int,
                      target_calls: int,
                      dispatches: Optional[int] = None) -> float:
        # sequential rounds keep the historical 3-tuple (tests pin the
        # timeline bitwise); parallel rounds append the measured device-
        # dispatch count so CostModel.t_dispatch can price the collapse.
        rnd = (kind, draft_steps, target_calls) if dispatches is None \
            else (kind, draft_steps, target_calls, dispatches)
        self.timeline.append(rnd)
        self.clock += self.cost.round_cost(rnd)
        if self.debug_check:
            self.pool.check()
        return self.clock

    # ----------------------------------------------- by-row lane staging
    def _by_row(self, dec: BatchedDecoder, seqs: List[_Seq],
                row_of: Callable[[_Seq], int]
                ) -> Tuple[np.ndarray, np.ndarray]:
        """(rids, ctrs) by decoder row for the tick functions; rows not
        owned by a listed request keep (0, 0) — their lanes compute
        garbage the host ignores."""
        rids = np.zeros(dec.n_rows, np.int32)
        ctrs = np.zeros(dec.n_rows, np.int32)
        for s in seqs:
            rids[row_of(s)] = s.rid
            ctrs[row_of(s)] = s.ctr
        return rids, ctrs


# ---------------------------------------------------------------------------
# batched SpS
# ---------------------------------------------------------------------------

class BatchedSpSEngine(BatchedEngineBase):
    """Vanilla speculative decoding, continuous-batched: gamma batched
    draft steps then one batched target verification per round — all
    device-resident.  Draft tokens chain from tick to tick as device
    arrays (the host never sees them mid-round); the round's only fetch is
    the (S, 3 + gamma) verdict packet."""
    name = "batched-sps"

    @_spanned("engine.round")
    def step_round(self) -> Dict[str, Any]:
        if self.ecfg.draft_mode == "parallel":
            # the sequential body below stays byte-identical (tests pin it
            # bitwise); parallel drafting is its own round function.
            return self._step_round_parallel()
        seqs = [s for s in self.active if not s.done]
        if not seqs:
            return {"committed": {}, "preempted": []}
        pred = self.predictor
        # per-request adaptive gamma from the acceptance history: each
        # request drafts/verifies its OWN g_i <= ecfg.gamma (ladder-
        # snapped); the round runs max(g_i) ticks with finished rows
        # parked.  Predictor off: g_i == gamma for every row and the round
        # below is byte-identical to the predictor-less code.
        for s in seqs:
            s.pdec = pred.decide(s.rid) if pred is not None else None
        g_of = {s.rid: (s.pdec.gamma if s.pdec is not None
                        else self.ecfg.gamma) for s in seqs}
        g = self.ecfg.gamma if pred is None \
            else max(g_of[s.rid] for s in seqs)
        rec = self.rec
        wall0 = rec.now()
        rnd_idx = len(self.timeline)

        def fits(ss):
            return (self.pools["d"].has_room(
                        [(("d", s.rid),
                          len(s.dft.pending) + g_of[s.rid] - 1)
                         for s in ss])
                    and self.pools["t"].has_room(
                        [(("t", s.rid), len(s.tgt.pending) + g_of[s.rid])
                         for s in ss]))

        with annotate("engine.make_room"):
            preempted = self._make_room(seqs, fits)
        if not seqs:
            return {"committed": {}, "preempted": preempted}
        n_d = self.dft_dec.n_rows
        B = self.max_batch

        # ---- draft stage: batched pending ingest + gamma sampling ticks,
        # sampled ids chained on device tick to tick
        with annotate("engine.draft_tick"):
            lg, _ = self._ingest(
                self.dft_dec,
                [(s.dft, ("d", s.rid), list(s.dft.pending)) for s in seqs])
            # pending lengths differ (1 after a reject, 2 after an all-accept):
            # read each row's logits at its REAL last token, not the pad
            last = np.zeros(n_d, np.int32)
            for s in seqs:
                last[s.dft.row] = len(s.dft.pending) - 1
                s.dft.pending = []
        tok_ticks, q_ticks = [], []
        for i in range(g):
            with annotate("engine.draft_tick"):
                # rows whose own g_i is exhausted park (rid/ctr 0 — their
                # lane computes garbage that glens masks out of the verify)
                ticking = [s for s in seqs if g_of[s.rid] > i]
                rids, ctrs = self._by_row(self.dft_dec, ticking,
                                          lambda s: s.dft.row)
                toks, qsl, _ = DL.tick_sample(lg, jnp.asarray(last),
                                              jnp.asarray(rids),
                                              jnp.asarray(ctrs), self._key,
                                              dtemp=self._dt, stemp=self._st,
                                              mesh=self.mesh)
                tok_ticks.append(toks)
                q_ticks.append(qsl)
                for s in ticking:
                    s.ctr += 1
                    s.stats.draft_tokens += 1
                if i < g - 1:
                    pairs = [(s.dft, ("d", s.rid)) for s in ticking
                             if g_of[s.rid] > i + 1]
                    if pairs:
                        lg, _ = self._ingest_dev(self.dft_dec, pairs, toks)
                        last[:] = 0
        tok_stack = jnp.stack(tok_ticks)          # (g, n_d) device
        q_stack = jnp.stack(q_ticks)              # (g, n_d, V) device
        wall_draft = rec.now()

        # ---- verify stage: ONE batched target call + fused device verdict
        with annotate("engine.verify_dispatch"):
            pends = {s.rid: list(s.tgt.pending) for s in seqs}
            npend = np.zeros(B, np.int32)
            pend_arr = np.zeros((B, 2), np.int32)
            trows = np.full(B, self.tgt_dec.n_rows, np.int32)  # OOB = pad lane
            drows = np.zeros(B, np.int32)
            rid_l = np.zeros(B, np.int32)
            ctr_l = np.zeros(B, np.int32)
            glens = np.zeros(B, np.int32)      # pad lanes: 0 (garbage, unread)
            for i, s in enumerate(seqs):
                p = pends[s.rid]
                npend[i] = len(p)
                pend_arr[i, :len(p)] = p
                trows[i] = s.tgt.row
                drows[i] = s.dft.row
                rid_l[i] = s.rid
                ctr_l[i] = s.ctr
                glens[i] = g_of[s.rid]
            Tb = DL.bucket(int((npend + glens).max()) if pred is not None
                           else int(npend.max()) + g)
            toks_full = DL.compose_verify_tokens(
                jnp.asarray(pend_arr), jnp.asarray(npend), tok_stack,
                jnp.asarray(drows), jnp.asarray(trows),
                n_rows=self.tgt_dec.n_rows, Tb=Tb)
            # staging mirrors _ingest/_batched for a device-composed token
            # frame: pool-extend by the REAL count, overflow-check the PADDED
            # width (same `p0 + T` rule _batched applies)
            pos = np.minimum(self.tgt_dec.row_pos,
                             self.tgt_dec.max_len - Tb).astype(np.int32)
            for s in seqs:
                self.pools["t"].extend(("t", s.rid),
                                       len(pends[s.rid]) + g_of[s.rid])
                if s.tgt.ing + Tb > self.tgt_dec.max_len:
                    raise RuntimeError(
                        f"row {s.tgt.row} overflows max_len")
                pos[s.tgt.row] = s.tgt.ing
            tlg, feats = self.tgt_dec.step(toks_full, pos)
            for s in seqs:
                s.tgt.ing += len(pends[s.rid]) + g_of[s.rid]
                self.tgt_dec.row_pos[s.tgt.row] = s.tgt.ing
            packet_dev = DL.sps_verify(
                tlg, q_stack, tok_stack, jnp.asarray(trows),
                jnp.asarray(drows), jnp.asarray(npend), jnp.asarray(rid_l),
                jnp.asarray(ctr_l), self._key,
                jnp.asarray(glens) if pred is not None else None,
                g=g, ttemp=self._tt,
                dtemp=self._dt, kernel=self._use_kernel,
                interpret=self._kernel_interpret, mesh=self.mesh)
            for s in seqs:
                s.ctr += g_of[s.rid] + 1
        pk = self._fetch(packet_dev)       # the round's ONLY host fetch
        wall_verify = rec.now()
        now = self.clock + self.cost.round_cost(("serial", g, 1))
        with annotate("engine.commit"):
            committed: Dict[int, int] = {}
            for i, s in enumerate(seqs):
                g_i = g_of[s.rid]
                n, nxt, all_acc = int(pk[i, 0]), int(pk[i, 1]), bool(pk[i, 2])
                dr = [int(x) for x in pk[i, 3:3 + g_i]]
                npend_i = len(pends[s.rid])
                before = min(len(s.out), s.max_new)
                s.stats.target_calls += 1
                s.feats_last = feats[:, s.tgt.row:s.tgt.row + 1,
                                     npend_i + g_i - 1, :]
                s.tgt.pending = []
                if pred is not None:
                    # update from the packet already on host: no extra syncs
                    pred.update(s.rid, all_acc, n / max(g_i, 1))
                if all_acc:
                    self._commit(s, dr + [nxt], now)
                    s.stats.run_extend(g_i + 1)
                    s.tgt.pending = [nxt]
                    s.dft.pending = [dr[-1], nxt]
                    if rec.enabled:
                        rec.spec(rid=s.rid, round=rnd_idx, stage="sps",
                                 committed=g_i + 1, accepted=g_i, drafted=g_i,
                                 cause="accept", gamma=g_i, bonus=True,
                                 pred=(s.pdec.obs() if s.pdec is not None
                                       else None), t=now)
                else:
                    self._commit(s, dr[:n] + [nxt], now)
                    s.stats.run_extend(n)
                    s.stats.run_break()
                    s.stats.rollback_tokens += g_i - n
                    self._rollback_streams(s)
                    if rec.enabled:
                        rec.spec(rid=s.rid, round=rnd_idx, stage="sps",
                                 committed=n + 1, accepted=n, drafted=g_i,
                                 rolled_back=g_i - n, cause="chunk-reject",
                                 gamma=g_i,
                                 pred=(s.pdec.obs() if s.pdec is not None
                                       else None), t=now)
                committed[s.rid] = min(len(s.out), s.max_new) - before
            if rec.enabled:
                wall1 = rec.now()
                rec.span("draft", wall0, wall_draft, engine=self.name)
                rec.span("verify", wall_draft, wall_verify, engine=self.name,
                         batch=len(seqs))
                rec.span("commit", wall_verify, wall1, engine=self.name)
                rec.round(engine=self.name, index=rnd_idx, mode="serial",
                          draft_steps=g, target_calls=1, batch=len(seqs),
                          wall0=wall0, wall1=wall1, t0=self.clock, t1=now)
            self._finish_round("serial", g, 1)
        return {"committed": committed, "preempted": preempted}

    def _step_round_parallel(self) -> Dict[str, Any]:
        """Single-pass parallel drafting round (DESIGN.md §7.12): the gamma
        sequential ticks collapse into ONE draft dispatch — each row's
        frame carries its pending tokens followed by g masked draft slots,
        and ``DL.draft_chunk`` reads every position's proposal off the one
        forward.  Verification is the sequential round's code unchanged:
        same verify frame, same PRNG coordinates per row (token i at
        (rid, ctr0 + i), verify window from ctr0 + g_i), same verdict
        packet — so the protocol is pinned equivalent and any quality
        difference is confined to the draft proposal distribution.

        The draft stream's cache holds the COMMITTED prefix only: drafted
        tokens never enter it (their hidden states came from slots), so an
        accept re-feeds the chunk as next round's pending and a reject
        replays the committed tail (see _rollback_streams)."""
        seqs = [s for s in self.active if not s.done]
        if not seqs:
            return {"committed": {}, "preempted": []}
        pred = self.predictor
        for s in seqs:
            s.pdec = pred.decide(s.rid) if pred is not None else None
        g_of = {s.rid: (s.pdec.gamma if s.pdec is not None
                        else self.ecfg.gamma) for s in seqs}
        g = self.ecfg.gamma if pred is None \
            else max(g_of[s.rid] for s in seqs)
        rec = self.rec
        wall0 = rec.now()
        rnd_idx = len(self.timeline)

        def fits(ss):
            # drafted tokens never enter the draft cache in this mode: the
            # draft pool grows by the pending re-ingest only
            return (self.pools["d"].has_room(
                        [(("d", s.rid), len(s.dft.pending)) for s in ss])
                    and self.pools["t"].has_room(
                        [(("t", s.rid), len(s.tgt.pending) + g_of[s.rid])
                         for s in ss]))

        with annotate("engine.make_room"):
            preempted = self._make_room(seqs, fits)
        if not seqs:
            return {"committed": {}, "preempted": preempted}
        n_d = self.dft_dec.n_rows
        B = self.max_batch
        calls0 = self.dft_dec.n_calls + self.tgt_dec.n_calls

        # ---- draft stage: ONE forward (pending ++ g slots per row), then
        # one fused chunk-sampling dispatch off its logits/features
        with annotate("engine.draft_tick"):
            P = {s.rid: len(s.dft.pending) for s in seqs}
            T = DL.bucket(max(P.values()) + g)
            toks = np.zeros((n_d, T), np.int32)
            nreal = np.zeros(n_d, np.int32)
            last = np.zeros(n_d, np.int32)
            pos = np.minimum(self.dft_dec.row_pos,
                             self.dft_dec.max_len - T).astype(np.int32)
            for s in seqs:
                p_i = P[s.rid]
                self.pools["d"].extend(("d", s.rid), p_i)
                if s.dft.ing + T > self.dft_dec.max_len:
                    raise RuntimeError(
                        f"row {s.dft.row} overflows max_len")
                toks[s.dft.row, :p_i] = s.dft.pending
                nreal[s.dft.row] = p_i
                last[s.dft.row] = p_i - 1
                pos[s.dft.row] = s.dft.ing
                s.dft.pending = []
            lg, dfeats = self.dft_dec.step_draft(
                toks, pos, nreal, self.draft_heads["mask_embed"])
            for s in seqs:
                s.dft.ing += P[s.rid]
                self.dft_dec.row_pos[s.dft.row] = s.dft.ing
            rids, ctrs = self._by_row(self.dft_dec, seqs, lambda s: s.dft.row)
            tok_stack, q_full, _ = DL.draft_chunk(
                lg, dfeats, self.dp["final_norm"], self.draft_heads["heads"],
                jnp.asarray(last), jnp.asarray(rids), jnp.asarray(ctrs),
                self._key, g=g, dtemp=self._dt, stemp=self._st,
                eps=self.dcfg.norm_eps, cap=self.dcfg.final_softcap,
                mesh=self.mesh)
            q_stack = q_full[:g]
            # PRNG parity: token i was drawn at (rid, ctr0 + i) — the exact
            # coordinates the sequential ticks consume.  Rows with g_i < g
            # sampled garbage at ctr0+g_i..ctr0+g-1; those draws are discarded
            # (glens masks them out of the verify), so the coordinate overlap
            # with the verify window below is harmless.
            for s in seqs:
                s.ctr += g_of[s.rid]
                s.stats.draft_tokens += g_of[s.rid]
        wall_draft = rec.now()

        # ---- verify stage: identical to the sequential round
        with annotate("engine.verify_dispatch"):
            pends = {s.rid: list(s.tgt.pending) for s in seqs}
            npend = np.zeros(B, np.int32)
            pend_arr = np.zeros((B, 2), np.int32)
            trows = np.full(B, self.tgt_dec.n_rows, np.int32)  # OOB = pad lane
            drows = np.zeros(B, np.int32)
            rid_l = np.zeros(B, np.int32)
            ctr_l = np.zeros(B, np.int32)
            glens = np.zeros(B, np.int32)      # pad lanes: 0 (garbage, unread)
            for i, s in enumerate(seqs):
                p = pends[s.rid]
                npend[i] = len(p)
                pend_arr[i, :len(p)] = p
                trows[i] = s.tgt.row
                drows[i] = s.dft.row
                rid_l[i] = s.rid
                ctr_l[i] = s.ctr
                glens[i] = g_of[s.rid]
            Tb = DL.bucket(int((npend + glens).max()) if pred is not None
                           else int(npend.max()) + g)
            toks_full = DL.compose_verify_tokens(
                jnp.asarray(pend_arr), jnp.asarray(npend), tok_stack,
                jnp.asarray(drows), jnp.asarray(trows),
                n_rows=self.tgt_dec.n_rows, Tb=Tb)
            pos_t = np.minimum(self.tgt_dec.row_pos,
                               self.tgt_dec.max_len - Tb).astype(np.int32)
            for s in seqs:
                self.pools["t"].extend(("t", s.rid),
                                       len(pends[s.rid]) + g_of[s.rid])
                if s.tgt.ing + Tb > self.tgt_dec.max_len:
                    raise RuntimeError(
                        f"row {s.tgt.row} overflows max_len")
                pos_t[s.tgt.row] = s.tgt.ing
            tlg, feats = self.tgt_dec.step(toks_full, pos_t)
            for s in seqs:
                s.tgt.ing += len(pends[s.rid]) + g_of[s.rid]
                self.tgt_dec.row_pos[s.tgt.row] = s.tgt.ing
            packet_dev = DL.sps_verify(
                tlg, q_stack, tok_stack, jnp.asarray(trows),
                jnp.asarray(drows), jnp.asarray(npend), jnp.asarray(rid_l),
                jnp.asarray(ctr_l), self._key,
                jnp.asarray(glens) if pred is not None else None,
                g=g, ttemp=self._tt,
                dtemp=self._dt, kernel=self._use_kernel,
                interpret=self._kernel_interpret, mesh=self.mesh)
            for s in seqs:
                s.ctr += g_of[s.rid] + 1
        pk = self._fetch(packet_dev)       # the round's ONLY host fetch
        wall_verify = rec.now()
        ndisp = self.dft_dec.n_calls + self.tgt_dec.n_calls - calls0
        now = self.clock + self.cost.round_cost(("serial", g, 1, ndisp))
        with annotate("engine.commit"):
            committed: Dict[int, int] = {}
            for i, s in enumerate(seqs):
                g_i = g_of[s.rid]
                n, nxt, all_acc = int(pk[i, 0]), int(pk[i, 1]), bool(pk[i, 2])
                dr = [int(x) for x in pk[i, 3:3 + g_i]]
                npend_i = len(pends[s.rid])
                before = min(len(s.out), s.max_new)
                s.stats.target_calls += 1
                s.feats_last = feats[:, s.tgt.row:s.tgt.row + 1,
                                     npend_i + g_i - 1, :]
                s.tgt.pending = []
                if pred is not None:
                    pred.update(s.rid, all_acc, n / max(g_i, 1))
                if all_acc:
                    self._commit(s, dr + [nxt], now)
                    s.stats.run_extend(g_i + 1)
                    s.tgt.pending = [nxt]
                    # the chunk never entered the draft cache: re-feed it whole
                    s.dft.pending = dr + [nxt]
                    if rec.enabled:
                        rec.spec(rid=s.rid, round=rnd_idx, stage="sps",
                                 committed=g_i + 1, accepted=g_i, drafted=g_i,
                                 cause="accept", gamma=g_i, bonus=True,
                                 dispatches=ndisp,
                                 pred=(s.pdec.obs() if s.pdec is not None
                                       else None), t=now)
                else:
                    self._commit(s, dr[:n] + [nxt], now)
                    s.stats.run_extend(n)
                    s.stats.run_break()
                    s.stats.rollback_tokens += g_i - n
                    self._rollback_streams(s)
                    if rec.enabled:
                        rec.spec(rid=s.rid, round=rnd_idx, stage="sps",
                                 committed=n + 1, accepted=n, drafted=g_i,
                                 rolled_back=g_i - n, cause="chunk-reject",
                                 gamma=g_i, dispatches=ndisp,
                                 pred=(s.pdec.obs() if s.pdec is not None
                                       else None), t=now)
                committed[s.rid] = min(len(s.out), s.max_new) - before
            if rec.enabled:
                wall1 = rec.now()
                rec.span("draft", wall0, wall_draft, engine=self.name)
                rec.span("verify", wall_draft, wall_verify, engine=self.name,
                         batch=len(seqs))
                rec.span("commit", wall_verify, wall1, engine=self.name)
                rec.round(engine=self.name, index=rnd_idx, mode="serial",
                          draft_steps=g, target_calls=1, batch=len(seqs),
                          dispatches=ndisp,
                          wall0=wall0, wall1=wall1, t0=self.clock, t1=now)
            self._finish_round("serial", g, 1, ndisp)
        return {"committed": committed, "preempted": preempted}


# ---------------------------------------------------------------------------
# batched SpecBranch
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _BranchSet:
    """Per-request branch-stage working set, alive within one round.
    Token ids and confidences are host ints/floats (from the per-tick
    packets); distributions stay device logits slices.  ``cont_q`` holds
    the RAW logits per continuation position — draft and signal
    temperatures are applied downstream, so one list serves both the
    chunk_q adoption and the q_b signal reads."""
    cands: np.ndarray                        # (k,)
    streams: List[_Stream] = dataclasses.field(default_factory=list)
    conts: List[List[int]] = dataclasses.field(default_factory=list)
    cont_q: List[List[jax.Array]] = dataclasses.field(default_factory=list)
    confs: List[List[float]] = dataclasses.field(default_factory=list)
    final_sig: List[Optional[jax.Array]] = dataclasses.field(
        default_factory=list)
    final_conf: List[float] = dataclasses.field(default_factory=list)


class BatchedSpecBranchEngine(BatchedEngineBase):
    """SpecBranch (hybrid drafting + branch parallelism), continuous-batched.

    Per global round every request advances one stage of the sequential
    engine's state machine (runtime/specbranch.py): DRAFT-mode requests
    serial-draft their chunk, BRANCH-mode requests fork k branch rows and
    draft continuations — all draft work rides the same batched single-token
    ticks — and one batched target call verifies every BRANCH-mode chunk.
    Requests in DRAFT mode simply skip the verify (their draft work is
    hidden under the other requests' verification, the Group-SD overlap).

    The target verification is DISPATCHED before the draft ticks run (the
    chunk under verification was drafted last round, so its tokens are
    already host-resident): on an async-dispatch backend the device chews
    the target forward + fused verdict while the host drives the draft
    ticks — the branch-parallel overlap of Sec. 5 realized at the dispatch
    layer.  The verdict packet ((S, 5) int32) is fetched only after the
    draft phase.

    Branch forks are row copies in the reference decoder, but page-table
    COW shares in the pool: the fork itself allocates zero pages and each
    branch pays only for its diverging continuation (Eq. 8).  Losing
    branches, doomed continuations and H-RAD-pruned suffixes all return
    their pages through ``truncate``/``close`` with a reason tag.
    """
    name = "batched-specbranch"

    def __init__(self, *args, **kw):
        ecfg = args[4] if len(args) > 4 else kw["ecfg"]
        self.draft_rows_per_seq = 1 + max(1, ecfg.k_max)
        super().__init__(*args, **kw)

    # ------------------------------------------------------------- helpers
    def _branch_k(self, seq: _Seq) -> int:
        if not self.ecfg.use_branch:
            return 1
        # the history predictor caps the hedge count; Eq. 7's confidence-
        # adaptive k still applies under the cap.  pdec None -> k_max cap,
        # exactly the predictor-less rule.
        cap = self.ecfg.k_max if seq.pdec is None \
            else min(self.ecfg.k_max, max(1, seq.pdec.k_cap))
        return min(cap, S.adaptive_k(seq.q_b_conf, cap))

    def _bkey(self, rid: int, i: int):
        return ("b", rid, i)

    def _free_branches(self, seq: _Seq, bset: _BranchSet,
                       reason: str, keep: Optional[int] = None) -> None:
        for i, st in enumerate(bset.streams):
            if i == keep:
                continue
            self.pools["d"].close(self._bkey(seq.rid, i), reason)
            self.dft_dec.unbind_row(st.row)
            self.dft_dec.free_rows.append(st.row)

    # --------------------------------------------------------------- round
    @_spanned("engine.round")
    def step_round(self) -> Dict[str, Any]:
        if self.ecfg.draft_mode == "parallel":
            # the sequential body below stays byte-identical (tests pin it
            # bitwise); parallel drafting is its own round function.
            return self._step_round_parallel()
        seqs = [s for s in self.active if not s.done]
        if not seqs:
            return {"committed": {}, "preempted": []}
        g, gb = self.ecfg.gamma, self.ecfg.gamma_branch
        K, CH = self._K, self._CH
        pred = self.predictor
        # one history-predictor decision per request per round — DRAFT-mode
        # rows use its gamma/epsilon for their stop rules, BRANCH-mode rows
        # its k cap (via _branch_k) and epsilon (posterior continuation
        # cut).  pdec stays None with the predictor off: every use below
        # falls back to the static ecfg knobs, bitwise-identical.
        for s in seqs:
            s.pdec = pred.decide(s.rid) if pred is not None else None
        g_of = {s.rid: (s.pdec.gamma if s.pdec is not None else g)
                for s in seqs}
        eps_of = {s.rid: (s.pdec.epsilon if s.pdec is not None
                          else self.ecfg.epsilon) for s in seqs}
        rec = self.rec
        wall0 = rec.now()
        rnd_idx = len(self.timeline)

        # has_room can't price not-yet-forked branch streams; count their
        # worst case (suffix pages + one COW tail copy each) by hand.
        def fits(ss):
            d_ups, t_ups, d_extra = [], [], 0
            pd = self.pools["d"]
            for s in ss:
                if s.mode == "draft":
                    d_ups.append((("d", s.rid),
                                  len(s.dft.pending) + g_of[s.rid]))
                else:
                    k = self._branch_k(s)
                    dlen = pd.length(("d", s.rid))
                    per = (pd.pages_for(dlen + 1 + gb)
                           - pd.pages_for(dlen) + 1)
                    d_extra += k * per
                    t_ups.append((("t", s.rid),
                                  len(s.tgt.pending) + len(s.chunk)))
            return (pd.would_need(d_ups) + d_extra <= pd.free_pages
                    and self.pools["t"].has_room(t_ups))

        with annotate("engine.make_room"):
            preempted = self._make_room(seqs, fits)

        serial = [s for s in seqs if s.mode == "draft"]
        branchers = [s for s in seqs if s.mode == "branch"]
        B = self.max_batch
        n_d = self.dft_dec.n_rows

        # ---- dispatch the branch-stage verification FIRST: the chunks
        # under verification were drafted last round, so the target
        # forward + fused verdict can overlap the draft ticks below
        # (JAX async dispatch — the paper's draft/verify parallelism).
        bsets: Dict[int, _BranchSet] = {}
        packet_dev = None
        tfeats = None
        pends: Dict[int, List[int]] = {}
        ks: Dict[int, int] = {}
        if branchers:
            with annotate("engine.branch_fork"):
                zero_v = jnp.zeros((self.dcfg.vocab_size,), jnp.float32)
                qb_rows = [s.q_b for s in branchers]
                qb_stack = jnp.stack(qb_rows
                                     + [zero_v] * (B - len(branchers)))
                rid_l = np.zeros(B, np.int32)
                ctr_l = np.zeros(B, np.int32)
                for i, s in enumerate(branchers):
                    rid_l[i] = s.rid
                    ctr_l[i] = s.ctr
                    ks[s.rid] = self._branch_k(s)
                cands = self._fetch(DL.draw_cands(
                    qb_stack, jnp.asarray(rid_l), jnp.asarray(ctr_l),
                    self._key, K=K, stemp=self._st,
                    mode=self.ecfg.branch_mode, mesh=self.mesh))
                if self.ecfg.branch_mode != "topk":
                    for s in branchers:
                        s.ctr += ks[s.rid]
                for i, s in enumerate(branchers):
                    bset = _BranchSet(
                        cands=cands[i, :ks[s.rid]].astype(np.int64))
                    for bi in range(ks[s.rid]):
                        row = self.dft_dec.free_rows.pop()
                        self.dft_dec.copy_row(s.dft.row, row)
                        self.pools["d"].fork(("d", s.rid),
                                             self._bkey(s.rid, bi))
                        self.dft_dec.bind_row(row, self._bkey(s.rid, bi))
                        bset.streams.append(_Stream(row=row, ing=s.dft.ing))
                        bset.conts.append([])
                        bset.cont_q.append([])
                        bset.confs.append([])
                        bset.final_sig.append(None)
                        bset.final_conf.append(0.0)
                    bsets[s.rid] = bset
            with annotate("engine.verify_dispatch"):
                pends = {s.rid: list(s.tgt.pending) for s in branchers}
                tlg, tfeats = self._ingest(
                    self.tgt_dec,
                    [(s.tgt, ("t", s.rid), s.tgt.pending + s.chunk)
                     for s in branchers])
                # fused chain + branch verdict (device); packet fetched after
                # the draft phase
                npend_l = np.zeros(B, np.int32)
                gch_l = np.zeros(B, np.int32)
                ks_l = np.ones(B, np.int32)
                trows = np.full(B, self.tgt_dec.n_rows, np.int32)  # OOB pad
                ctr_v = np.zeros(B, np.int32)
                cq_rows, ct_rows = [], []
                zero_q = jnp.zeros((CH, self.dcfg.vocab_size), jnp.float32)
                for i, s in enumerate(branchers):
                    npend_l[i] = len(pends[s.rid])
                    gch_l[i] = len(s.chunk)
                    ks_l[i] = ks[s.rid]
                    trows[i] = s.tgt.row
                    ctr_v[i] = s.ctr
                    if s.chunk_q:
                        cq = jnp.stack(
                            list(s.chunk_q)
                            + [s.chunk_q[-1]] * (CH - len(s.chunk_q)))
                    else:
                        cq = zero_q
                    cq_rows.append(cq)
                    ct = np.zeros(CH, np.int32)
                    ct[:len(s.chunk)] = s.chunk
                    ct_rows.append(ct)
                cq_rows += [zero_q] * (B - len(branchers))
                ct_rows += [np.zeros(CH, np.int32)] * (B - len(branchers))
                packet_dev = DL.branch_verify(
                    tlg, jnp.asarray(trows), jnp.asarray(npend_l),
                    jnp.asarray(gch_l), jnp.stack(cq_rows),
                    jnp.asarray(np.stack(ct_rows)), jnp.asarray(cands),
                    jnp.asarray(ks_l), qb_stack, jnp.asarray(rid_l),
                    jnp.asarray(ctr_v), self._key, CH=CH, K=K,
                    ttemp=self._tt, dtemp=self._dt, stemp=self._st,
                    kernel=self._use_kernel,
                    interpret=self._kernel_interpret, mesh=self.mesh)
                for s in branchers:
                    s.ctr += self._W
        wall_disp = rec.now()

        # ---- PHASE A: all draft-model work, interleaved batched ticks ----
        with annotate("engine.draft_tick"):
            # H-RAD prior signal decides each DRAFT-mode request's stop rule.
            sig: Dict[int, int] = {}
            for s in serial:
                e_tok = s.dft.pending[0] if s.dft.pending else s.tgt.pending[0]
                sig[s.rid] = (self._hrad_signal(s, e_tok)
                              if self.ecfg.use_hrad else 1)
                s.chunk, s.chunk_q = [], []

            # tick 0: serial rows ingest pending; branch rows ingest candidates
            triples = []
            for s in serial:
                triples.append((s.dft, ("d", s.rid), list(s.dft.pending)))
                s.dft.pending = []
            for s in branchers:
                bset = bsets[s.rid]
                for i, st in enumerate(bset.streams):
                    triples.append((st, self._bkey(s.rid, i),
                                    [int(bset.cands[i])]))
                s.stats.draft_tokens += 1      # batched candidate ingest step
            lg, _ = self._ingest(self.dft_dec, triples)
            last = np.zeros(n_d, np.int32)
            for st, _, toks in triples:
                last[st.row] = len(toks) - 1
        ticks = 1

        # Double-buffered tick pipeline (ROADMAP PR 4 remainder): tick t's
        # sampling is DISPATCHED before tick t-1's [token, conf] packet is
        # fetched, so the draft phase's one blocking fetch overlaps the
        # device computing the next tick.  Stop decisions therefore land
        # one tick late: epsilon stops are applied OPTIMISTICALLY — the
        # row's sample is ingested as if it kept drafting, and when the
        # packet says it should have stopped, the one over-ingested token
        # is pruned exactly like any rollback (positional reset + page
        # reclaim).  Deterministic stops (sig == 0, chunk length == gamma,
        # branch tick counts) never over-ingest.  Uniform coordinates are
        # staged from per-request bases (ctr0 + own tick index), identical
        # to the resolved-counter consumption, so streams stay
        # batch-composition independent.
        live = {s.rid: True for s in serial}
        reads = {s.rid: 0 for s in serial}     # ticks staged so far
        ctr0 = {s.rid: s.ctr for s in serial}
        b_ctr0 = {s.rid: s.ctr for s in branchers}
        branch_j = {s.rid: 0 for s in branchers}

        @_spanned("engine.draft_resolve")
        def resolve(p) -> None:
            """Apply one fetched tick packet: keep/stop serial chunks
            (pruning an optimistic over-ingest on epsilon stops), record
            branch continuations."""
            _, qsl_p, packed_p, srd, brd = p
            pkt = self._fetch(packed_p)         # (n_d, 2) f32 — tiny
            for s, i in srd:
                if not live[s.rid]:
                    continue            # trailing read past its own stop
                row = s.dft.row
                conf = float(pkt[row, 1])
                over = False
                if sig[s.rid] == 0 or i >= g_of[s.rid]:
                    stop = True                  # deterministic: no ingest
                elif sig[s.rid] == 1 and conf < eps_of[s.rid]:
                    stop = True
                    over = True                  # token i rode optimism
                else:
                    stop = False
                if stop:
                    s.q_b = qsl_p[row]
                    s.q_b_conf = conf
                    s.stats.draft_tokens += len(s.chunk) + 1
                    live[s.rid] = False
                    if over:
                        # rollback-aware un-ingest of the speculative token
                        self.pools["d"].truncate(("d", s.rid),
                                                 s.dft.ing - 1, "prune")
                        s.dft.ing -= 1
                        self.dft_dec.row_pos[s.dft.row] = s.dft.ing
                    if rec.enabled:
                        rec.spec(rid=s.rid, round=rnd_idx, stage="draft",
                                 drafted=len(s.chunk) + 1,
                                 gamma=g_of[s.rid],
                                 eps_stop=over,
                                 hrad=(sig[s.rid] if self.ecfg.use_hrad
                                       else None),
                                 pred=(s.pdec.obs() if s.pdec is not None
                                       else None),
                                 t=self.clock)
                    continue
                s.chunk.append(int(pkt[row, 0]))
                s.chunk_q.append(qsl_p[row])
                s.ctr += 1
            for s, j in brd:
                bset = bsets[s.rid]
                if j == gb:
                    for i, st in enumerate(bset.streams):
                        bset.final_sig[i] = qsl_p[st.row]
                        bset.final_conf[i] = float(pkt[st.row, 1])
                    continue
                for i, st in enumerate(bset.streams):
                    row = st.row
                    bset.conts[i].append(int(pkt[row, 0]))
                    bset.cont_q[i].append(qsl_p[row])
                    bset.confs[i].append(float(pkt[row, 1]))
                s.stats.draft_tokens += 1
                s.ctr += len(bset.streams)

        pend = None        # the dispatched-but-unresolved tick
        while True:
            # which rows read a tick now?  (live lags one tick for epsilon
            # stops — the extra read samples garbage the resolve skips)
            readers = [s for s in serial
                       if live[s.rid] and reads[s.rid] <= g_of[s.rid]
                       and not (sig[s.rid] == 0 and reads[s.rid] >= 1)]
            br_read = [s for s in branchers if branch_j[s.rid] <= gb]
            if not readers and not br_read:
                if pend is not None:
                    resolve(pend)               # drain the pipeline
                    pend = None
                    continue
                break
            with annotate("engine.draft_tick"):
                rids = np.zeros(n_d, np.int32)
                ctrs = np.zeros(n_d, np.int32)
                srd = []
                for s in readers:
                    i = reads[s.rid]
                    rids[s.dft.row] = s.rid
                    ctrs[s.dft.row] = ctr0[s.rid] + i
                    srd.append((s, i))
                    reads[s.rid] = i + 1
                brd = []
                for s in br_read:
                    j = branch_j[s.rid]
                    k_s = len(bsets[s.rid].streams)
                    for i, st in enumerate(bsets[s.rid].streams):
                        rids[st.row] = s.rid
                        # branch lane i draws uniform (rid, base + j*k + i):
                        # the request consumes its OWN k per tick
                        ctrs[st.row] = b_ctr0[s.rid] + j * k_s + i
                    brd.append((s, j))
                    branch_j[s.rid] = j + 1
                toks_dev, qsl, packed = DL.tick_sample(
                    lg, jnp.asarray(last), jnp.asarray(rids),
                    jnp.asarray(ctrs), self._key, dtemp=self._dt,
                    stemp=self._st, mesh=self.mesh)
            # fetch the PREVIOUS tick's packet while this tick computes
            if pend is not None:
                resolve(pend)
            pend = (toks_dev, qsl, packed, srd, brd)
            with annotate("engine.draft_tick"):
                # optimistic ingest: every row still (believed) drafting
                # chains its sample straight into the next forward
                ingest_pairs = []
                for s, i in srd:
                    if live[s.rid] and sig[s.rid] != 0 and i < g_of[s.rid]:
                        ingest_pairs.append((s.dft, ("d", s.rid)))
                for s, j in brd:
                    if j < gb:
                        for i, st in enumerate(bsets[s.rid].streams):
                            ingest_pairs.append((st, self._bkey(s.rid, i)))
                if ingest_pairs:
                    lg, _ = self._ingest_dev(self.dft_dec, ingest_pairs,
                                             toks_dev)
                    last[:] = 0
                    ticks += 1

        # ---- PHASE B: fetch the verdict packet, commit per brancher ----
        wall_draft1 = rec.now()
        committed: Dict[int, int] = {}
        n_target = 1 if branchers else 0
        kind = "parallel" if (branchers and self.ecfg.use_branch) \
            else "serial"
        now = self.clock + self.cost.round_cost((kind, ticks, n_target))
        wall_vfetch = wall_draft1
        if branchers:
            pk = self._fetch(packet_dev)
            wall_vfetch = rec.now()
        with annotate("engine.commit"):
            for i, s in enumerate(branchers):
                s.tgt.pending = []
                before = min(len(s.out), s.max_new)
                self._branch_verdict(s, bsets[s.rid], pk[i], tfeats,
                                     len(pends[s.rid]), now)
                committed[s.rid] = min(len(s.out), s.max_new) - before
            for s in serial:
                s.mode = "branch"
            if rec.enabled:
                wall1 = rec.now()
                rec.span("draft", wall_disp, wall_draft1, engine=self.name,
                         ticks=ticks)
                if branchers:
                    # dispatched before the draft phase, fetched after it: the
                    # verify span overlapping the draft span is the paper's
                    # hidden verification, visible in Perfetto
                    rec.span("verify", wall0, wall_vfetch, engine=self.name,
                             batch=len(branchers))
                    rec.span("commit", wall_vfetch, wall1, engine=self.name)
                rec.round(engine=self.name, index=rnd_idx, mode=kind,
                          draft_steps=ticks, target_calls=n_target,
                          batch=len(seqs), wall0=wall0, wall1=wall1,
                          t0=self.clock, t1=now)
            self._finish_round(kind, ticks, n_target)
        return {"committed": committed, "preempted": preempted}

    def _step_round_parallel(self) -> Dict[str, Any]:
        """Single-pass parallel drafting round for SpecBranch (DESIGN.md
        §7.12).  The interleaved single-token tick pipeline collapses into
        ONE shared draft dispatch: each serial row's frame carries its
        pending tokens plus G masked slots, each branch lane's frame the
        parent chunk + its candidate plus G slots (the chunk never entered
        the parent's cache — parallel mode keeps draft caches at the
        committed prefix), and ``DL.draft_chunk`` reads every proposal off
        that one forward.  Stop rules (H-RAD prior, epsilon, gamma) are
        applied post-hoc on the fetched [token, conf] packet — confidences
        for EVERY position are already host-resident, so no optimistic
        over-ingest and no prune is needed.  The dispatch-first branch
        verification is unchanged: same verdict packets, same PRNG windows,
        so the rollback protocol is pinned equivalent to sequential mode.

        PRNG: serial chunk token i draws at (rid, ctr0 + i) exactly like
        the sequential ticks; branch lane i draws its continuation as a
        contiguous block (rid, b_ctr0 + i*gb + j) — the union over lanes is
        the same coordinate set sequential's j*k + i interleaving consumes,
        so cross-round uniqueness of USED coordinates and batch-composition
        independence both hold (garbage draws past a row's own use may
        overlap later windows; they are discarded unread)."""
        seqs = [s for s in self.active if not s.done]
        if not seqs:
            return {"committed": {}, "preempted": []}
        g, gb = self.ecfg.gamma, self.ecfg.gamma_branch
        K, CH = self._K, self._CH
        G = max(g, gb)
        pred = self.predictor
        for s in seqs:
            s.pdec = pred.decide(s.rid) if pred is not None else None
        g_of = {s.rid: (s.pdec.gamma if s.pdec is not None else g)
                for s in seqs}
        eps_of = {s.rid: (s.pdec.epsilon if s.pdec is not None
                          else self.ecfg.epsilon) for s in seqs}
        rec = self.rec
        wall0 = rec.now()
        rnd_idx = len(self.timeline)

        def fits(ss):
            # serial draft streams grow by the pending re-ingest only
            # (drafted tokens never enter the cache); branch lanes ingest
            # chunk + candidate each (gb kept as conservative margin).
            d_ups, t_ups, d_extra = [], [], 0
            pd = self.pools["d"]
            for s in ss:
                if s.mode == "draft":
                    d_ups.append((("d", s.rid), len(s.dft.pending)))
                else:
                    k = self._branch_k(s)
                    dlen = pd.length(("d", s.rid))
                    per = (pd.pages_for(dlen + 1 + len(s.chunk) + gb)
                           - pd.pages_for(dlen) + 1)
                    d_extra += k * per
                    t_ups.append((("t", s.rid),
                                  len(s.tgt.pending) + len(s.chunk)))
            return (pd.would_need(d_ups) + d_extra <= pd.free_pages
                    and self.pools["t"].has_room(t_ups))

        with annotate("engine.make_room"):
            preempted = self._make_room(seqs, fits)

        serial = [s for s in seqs if s.mode == "draft"]
        branchers = [s for s in seqs if s.mode == "branch"]
        B = self.max_batch
        n_d = self.dft_dec.n_rows
        calls0 = self.dft_dec.n_calls + self.tgt_dec.n_calls

        # ---- dispatch the branch-stage verification FIRST (identical to
        # the sequential round: the chunk under verification was drafted
        # last round, so the verdict overlaps the draft dispatch below)
        bsets: Dict[int, _BranchSet] = {}
        packet_dev = None
        tfeats = None
        pends: Dict[int, List[int]] = {}
        ks: Dict[int, int] = {}
        if branchers:
            with annotate("engine.branch_fork"):
                zero_v = jnp.zeros((self.dcfg.vocab_size,), jnp.float32)
                qb_rows = [s.q_b for s in branchers]
                qb_stack = jnp.stack(qb_rows
                                     + [zero_v] * (B - len(branchers)))
                rid_l = np.zeros(B, np.int32)
                ctr_l = np.zeros(B, np.int32)
                for i, s in enumerate(branchers):
                    rid_l[i] = s.rid
                    ctr_l[i] = s.ctr
                    ks[s.rid] = self._branch_k(s)
                cands = self._fetch(DL.draw_cands(
                    qb_stack, jnp.asarray(rid_l), jnp.asarray(ctr_l),
                    self._key, K=K, stemp=self._st,
                    mode=self.ecfg.branch_mode, mesh=self.mesh))
                if self.ecfg.branch_mode != "topk":
                    for s in branchers:
                        s.ctr += ks[s.rid]
                for i, s in enumerate(branchers):
                    bset = _BranchSet(
                        cands=cands[i, :ks[s.rid]].astype(np.int64))
                    for bi in range(ks[s.rid]):
                        row = self.dft_dec.free_rows.pop()
                        self.dft_dec.copy_row(s.dft.row, row)
                        self.pools["d"].fork(("d", s.rid),
                                             self._bkey(s.rid, bi))
                        self.dft_dec.bind_row(row, self._bkey(s.rid, bi))
                        bset.streams.append(_Stream(row=row, ing=s.dft.ing))
                        bset.conts.append([])
                        bset.cont_q.append([])
                        bset.confs.append([])
                        bset.final_sig.append(None)
                        bset.final_conf.append(0.0)
                    bsets[s.rid] = bset
            with annotate("engine.verify_dispatch"):
                pends = {s.rid: list(s.tgt.pending) for s in branchers}
                tlg, tfeats = self._ingest(
                    self.tgt_dec,
                    [(s.tgt, ("t", s.rid), s.tgt.pending + s.chunk)
                     for s in branchers])
                npend_l = np.zeros(B, np.int32)
                gch_l = np.zeros(B, np.int32)
                ks_l = np.ones(B, np.int32)
                trows = np.full(B, self.tgt_dec.n_rows, np.int32)  # OOB pad
                ctr_v = np.zeros(B, np.int32)
                cq_rows, ct_rows = [], []
                zero_q = jnp.zeros((CH, self.dcfg.vocab_size), jnp.float32)
                for i, s in enumerate(branchers):
                    npend_l[i] = len(pends[s.rid])
                    gch_l[i] = len(s.chunk)
                    ks_l[i] = ks[s.rid]
                    trows[i] = s.tgt.row
                    ctr_v[i] = s.ctr
                    if s.chunk_q:
                        cq = jnp.stack(
                            list(s.chunk_q)
                            + [s.chunk_q[-1]] * (CH - len(s.chunk_q)))
                    else:
                        cq = zero_q
                    cq_rows.append(cq)
                    ct = np.zeros(CH, np.int32)
                    ct[:len(s.chunk)] = s.chunk
                    ct_rows.append(ct)
                cq_rows += [zero_q] * (B - len(branchers))
                ct_rows += [np.zeros(CH, np.int32)] * (B - len(branchers))
                packet_dev = DL.branch_verify(
                    tlg, jnp.asarray(trows), jnp.asarray(npend_l),
                    jnp.asarray(gch_l), jnp.stack(cq_rows),
                    jnp.asarray(np.stack(ct_rows)), jnp.asarray(cands),
                    jnp.asarray(ks_l), qb_stack, jnp.asarray(rid_l),
                    jnp.asarray(ctr_v), self._key, CH=CH, K=K,
                    ttemp=self._tt, dtemp=self._dt, stemp=self._st,
                    kernel=self._use_kernel,
                    interpret=self._kernel_interpret, mesh=self.mesh)
                for s in branchers:
                    s.ctr += self._W
        wall_disp = rec.now()

        # ---- PHASE A: ONE shared draft dispatch for every row ----
        with annotate("engine.draft_tick"):
            sig: Dict[int, int] = {}
            for s in serial:
                e_tok = (s.dft.pending[-1] if s.dft.pending
                         else s.tgt.pending[-1])
                sig[s.rid] = (self._hrad_signal(s, e_tok)
                              if self.ecfg.use_hrad else 1)
                s.chunk, s.chunk_q = [], []

            reals: List[Tuple[_Stream, Any, List[int]]] = []
            for s in serial:
                reals.append((s.dft, ("d", s.rid), list(s.dft.pending)))
                s.dft.pending = []
            for s in branchers:
                bset = bsets[s.rid]
                for i, st in enumerate(bset.streams):
                    # the chunk never entered the parent's cache: each
                    # lane ingests it plus its own candidate (win.ing then
                    # equals the committed count after an adopt —
                    # _branch_verdict and _prune_draft work unchanged)
                    reals.append((st, self._bkey(s.rid, i),
                                  list(s.chunk) + [int(bset.cands[i])]))
                s.stats.draft_tokens += 1      # candidate ingest
            T = DL.bucket(max(len(t) for _, _, t in reals) + G)
            toks = np.zeros((n_d, T), np.int32)
            nreal = np.zeros(n_d, np.int32)
            last = np.zeros(n_d, np.int32)
            pos = np.minimum(self.dft_dec.row_pos,
                             self.dft_dec.max_len - T).astype(np.int32)
            for st, key, t in reals:
                self._pool_of(key).extend(key, len(t))
                if st.ing + T > self.dft_dec.max_len:
                    raise RuntimeError(f"row {st.row} overflows max_len")
                toks[st.row, :len(t)] = t
                nreal[st.row] = len(t)
                last[st.row] = len(t) - 1
                pos[st.row] = st.ing
            lg, dfeats = self.dft_dec.step_draft(
                toks, pos, nreal, self.draft_heads["mask_embed"])
            for st, _, t in reals:
                st.ing += len(t)
                self.dft_dec.row_pos[st.row] = st.ing
            rids = np.zeros(n_d, np.int32)
            ctrs = np.zeros(n_d, np.int32)
            for s in serial:
                rids[s.dft.row] = s.rid
                ctrs[s.dft.row] = s.ctr
            for s in branchers:
                for i, st in enumerate(bsets[s.rid].streams):
                    rids[st.row] = s.rid
                    ctrs[st.row] = s.ctr + i * gb
            tok_stack, q_full, packed = DL.draft_chunk(
                lg, dfeats, self.dp["final_norm"], self.draft_heads["heads"],
                jnp.asarray(last), jnp.asarray(rids), jnp.asarray(ctrs),
                self._key, g=G, dtemp=self._dt, stemp=self._st,
                eps=self.dcfg.norm_eps, cap=self.dcfg.final_softcap,
                mesh=self.mesh)
        with annotate("engine.draft_resolve"):
            pkt = self._fetch(packed)          # (n_d, G+1, 2) [token, conf]
            ticks = 1

            # post-hoc stop rules, serial rows: confidences for every
            # position are on host — pick the stop point directly, no
            # optimistic ingest
            for s in serial:
                row = s.dft.row
                g_i = g_of[s.rid]
                if sig[s.rid] == 0:
                    stop_j = 0
                elif sig[s.rid] == 1:
                    stop_j = next((j for j in range(g_i)
                                   if float(pkt[row, j, 1]) < eps_of[s.rid]),
                                  g_i)
                else:
                    stop_j = g_i
                s.chunk = [int(pkt[row, j, 0]) for j in range(stop_j)]
                s.chunk_q = [q_full[j, row] for j in range(stop_j)]
                s.q_b = q_full[stop_j, row]
                s.q_b_conf = float(pkt[row, stop_j, 1])
                s.ctr += stop_j
                s.stats.draft_tokens += stop_j + 1
                if rec.enabled:
                    rec.spec(rid=s.rid, round=rnd_idx, stage="draft",
                             drafted=stop_j + 1, gamma=g_i,
                             eps_stop=(sig[s.rid] == 1 and stop_j < g_i),
                             hrad=(sig[s.rid] if self.ecfg.use_hrad else None),
                             pred=(s.pdec.obs() if s.pdec is not None
                                   else None),
                             t=self.clock)
            # branch lanes: continuation tokens/confidences off the same packet
            for s in branchers:
                bset = bsets[s.rid]
                for i, st in enumerate(bset.streams):
                    row = st.row
                    bset.conts[i] = [int(pkt[row, j, 0]) for j in range(gb)]
                    bset.cont_q[i] = [q_full[j, row] for j in range(gb)]
                    bset.confs[i] = [float(pkt[row, j, 1]) for j in range(gb)]
                    bset.final_sig[i] = q_full[gb, row]
                    bset.final_conf[i] = float(pkt[row, gb, 1])
                s.stats.draft_tokens += gb
                s.ctr += len(bset.streams) * gb

        # ---- PHASE B: fetch the verdict packet, commit per brancher ----
        wall_draft1 = rec.now()
        committed: Dict[int, int] = {}
        n_target = 1 if branchers else 0
        kind = "parallel" if (branchers and self.ecfg.use_branch) \
            else "serial"
        ndisp = self.dft_dec.n_calls + self.tgt_dec.n_calls - calls0
        now = self.clock + self.cost.round_cost((kind, ticks, n_target,
                                                 ndisp))
        wall_vfetch = wall_draft1
        if branchers:
            pk = self._fetch(packet_dev)
            wall_vfetch = rec.now()
        with annotate("engine.commit"):
            for i, s in enumerate(branchers):
                s.tgt.pending = []
                before = min(len(s.out), s.max_new)
                self._branch_verdict(s, bsets[s.rid], pk[i], tfeats,
                                     len(pends[s.rid]), now)
                committed[s.rid] = min(len(s.out), s.max_new) - before
            for s in serial:
                s.mode = "branch"
            if rec.enabled:
                wall1 = rec.now()
                rec.span("draft", wall_disp, wall_draft1, engine=self.name,
                         ticks=ticks)
                if branchers:
                    rec.span("verify", wall0, wall_vfetch, engine=self.name,
                             batch=len(branchers))
                    rec.span("commit", wall_vfetch, wall1, engine=self.name)
                rec.round(engine=self.name, index=rnd_idx, mode=kind,
                          draft_steps=ticks, target_calls=n_target,
                          batch=len(seqs), dispatches=ndisp,
                          wall0=wall0, wall1=wall1,
                          t0=self.clock, t1=now)
            self._finish_round(kind, ticks, n_target, ndisp)
        return {"committed": committed, "preempted": preempted}

    # --------------------------------------------------- verdict (packet)
    def _branch_verdict(self, s: _Seq, bset: _BranchSet, pk_row, feats,
                        npend: int, now: float) -> None:
        """Commit/rollback bookkeeping from the (5,) int32 verdict packet
        [n_acc, chain_next, all_acc, accepted_branch, branch_token] — the
        distributions that produced it never left the device."""
        gb = self.ecfg.gamma_branch
        gchunk = len(s.chunk)
        n_acc, chain_next, all_acc, acc_b, tok_bd = (int(x) for x in pk_row)
        s.stats.target_calls += 1
        s.feats_last = feats[:, s.tgt.row:s.tgt.row + 1,
                             npend + gchunk - 1, :]
        pred = self.predictor
        pobs = s.pdec.obs() if s.pdec is not None else None
        eps_i = s.pdec.epsilon if s.pdec is not None else self.ecfg.epsilon
        if pred is not None:
            # both outcomes come from the verdict packet already on host
            if gchunk > 0:
                pred.update(s.rid, bool(all_acc), n_acc / gchunk)
            if all_acc:
                pred.update(s.rid, acc_b >= 0)

        if not all_acc:
            # mid-chunk rejection: every branch is doomed (Fig. 1a)
            self._commit(s, s.chunk[:n_acc] + [chain_next], now)
            s.stats.run_extend(n_acc)
            s.stats.run_break()
            s.stats.rollback_tokens += (gchunk - n_acc) + gb
            self._free_branches(s, bset, "rollback")
            self._rollback_streams(s)
            if self.rec.enabled:
                self.rec.spec(rid=s.rid, round=len(self.timeline),
                              stage="branch", committed=n_acc + 1,
                              accepted=n_acc,
                              rolled_back=(gchunk - n_acc) + gb,
                              cause="chunk-reject", gamma=gchunk,
                              k=len(bset.streams), pred=pobs, t=now)
            s.mode, s.chunk, s.chunk_q, s.q_b = "draft", [], [], None
            return

        if acc_b < 0:
            # no branch survives: emit the residual, drop continuations
            self._commit(s, s.chunk + [tok_bd], now)
            s.stats.run_extend(gchunk)
            s.stats.run_break()
            s.stats.rollback_tokens += gb
            self._free_branches(s, bset, "branch")
            self._rollback_streams(s)
            if self.rec.enabled:
                self.rec.spec(rid=s.rid, round=len(self.timeline),
                              stage="branch", committed=gchunk + 1,
                              accepted=gchunk, rolled_back=gb,
                              cause="branch-miss", gamma=gchunk,
                              k=len(bset.streams), pred=pobs, t=now)
            s.mode, s.chunk, s.chunk_q, s.q_b = "draft", [], [], None
            return

        i = acc_b
        tok_b = tok_bd
        self._commit(s, s.chunk + [tok_b], now)
        s.stats.run_extend(gchunk + 1)
        s.tgt.pending = [tok_b]
        # adopt the winning branch: its row becomes the draft row, its page
        # table replaces the parent's (shared prefix transfers refcounts)
        win = bset.streams[i]
        self.dft_dec.copy_row(win.row, s.dft.row)
        s.dft.ing = win.ing
        s.dft.pending = []
        self.pools["d"].adopt(("d", s.rid), self._bkey(s.rid, i))
        self._free_branches(s, bset, "branch", keep=i)
        self.dft_dec.unbind_row(win.row)
        self.dft_dec.free_rows.append(win.row)

        # posterior H-RAD on THIS verification's features (Sec. 5.2)
        sgn = (self._hrad_signal(s, tok_b) if self.ecfg.use_hrad else 1)
        cont, q_i = bset.conts[i], bset.cont_q[i]
        confs = bset.confs[i]
        pruned = 0
        if sgn == 2:
            s.chunk, s.chunk_q = list(cont), list(q_i)
            s.q_b = bset.final_sig[i]
            s.q_b_conf = bset.final_conf[i]
        elif sgn == 0:
            # prune the whole continuation; branch at its first token
            s.chunk, s.chunk_q = [], []
            s.q_b = q_i[0]
            s.q_b_conf = confs[0]
            s.stats.pruned_tokens += gb
            pruned = gb
            self._prune_draft(s, s.committed)
        else:
            j = next((jj for jj in range(gb)
                      if confs[jj] < eps_i), gb)
            if j == gb:
                s.chunk, s.chunk_q = list(cont), list(q_i)
                s.q_b = bset.final_sig[i]
                s.q_b_conf = bset.final_conf[i]
            else:
                s.chunk, s.chunk_q = list(cont[:j]), list(q_i[:j])
                s.q_b = q_i[j]
                s.q_b_conf = confs[j]
                s.stats.pruned_tokens += gb - j
                pruned = gb - j
                self._prune_draft(s, s.committed + j)
        s.mode = "branch"
        if self.rec.enabled:
            self.rec.spec(rid=s.rid, round=len(self.timeline),
                          stage="branch", committed=gchunk + 1,
                          accepted=gchunk + 1, pruned=pruned,
                          cause="branch-adopt", gamma=gchunk,
                          k=len(bset.streams),
                          hrad=sgn if self.ecfg.use_hrad else None,
                          pred=pobs, t=now)

    def _prune_draft(self, s: _Seq, keep: int) -> None:
        """H-RAD pre-verify pruning: positional reset of the draft stream."""
        if s.dft.ing > keep:
            self.pools["d"].truncate(("d", s.rid), keep, "prune")
            s.dft.ing = keep
        self.dft_dec.row_pos[s.dft.row] = s.dft.ing   # see _rollback_streams
        s.dft.pending = []
