"""DecodeState: composable per-row decode-state backend layer
(DESIGN.md §7.8).

The batched serving engines juggle three divergent storage layouts — dense
N-row attention caches, physically paged attention tables, and per-row SSM
checkpoint rings — and through PR 4 the layout logic lived as if/else
chains inside ``BatchedDecoder``, which is why the paged backend simply
rejected hybrid configs.  This module factors the layouts into *state
components* behind one interface, so a decoder's cache is a mixed pytree
assembled from components instead of branches:

  * ``DenseAttnState``  — N-row dense KV rows (global and sliding-window
    rings), the reference layout;
  * ``PagedAttnState``  — attention KV scattered across a ``PagedKVPool``'s
    pages, addressed per call through page-table views (zero-copy COW
    branch forks, page-granular rollback);
  * ``SSMRingState``    — per-row position-indexed checkpoint rings for
    recurrent (mamba) slots, the §7.6 rollback substrate.

``DecodeState`` composes whichever components a (config, backend) pair
needs and exposes the uniform per-row contract the engines program
against::

    alloc / bind / prefill / append / rollback(pos) / snapshot / restore
    / fork (COW) / pack_row / unpack_row

Rollback is *positional* for every component — shrink the row's logical
length, reset its write head, and the next forward resumes exactly
(attention masks stale slots causally, pools reclaim whole pages, rings
reload the accept-point checkpoint) — which is what makes the mixed tree
serve hybrid configs on the paged backend: paged attention slots and
per-row mamba rings roll back through the same call.

Swap (preemption) layout: the attention half of a row packs to ``(L,
swap_dim)`` float32 token rows (dense rows sliced, paged rows gathered
page-by-page through the table); recurrent state is position-indexed, not
token rows, so on the paged backend it rides the preemption metadata as a
single ring checkpoint (``snapshot``/``restore``).  The dense backend
keeps its PR 3 behavior — hybrid rows recompute their prefix at
re-admission — because the dense path is the reference oracle the paged
swap is checked against.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M
from repro.models.config import ModelConfig
from repro.serving.kv_pool import PagedKVPool
from repro.sharding import rules as _rules

__all__ = ["DecodeState", "DenseAttnState", "PagedAttnState",
           "SSMRingState", "iter_slots"]


def iter_slots(cache):
    """Slot cache dicts of a decode-cache pytree in stable (blocks, rem)
    order — the addressing every component shares."""
    for c in cache["blocks"]:
        yield c
    for c in cache["rem"]:
        yield c


def _fresh_like(a: jax.Array, lanes: int) -> jax.Array:
    """A fresh-row buffer with the batch axis (axis 1) resized to
    ``lanes``: integer leaves fill with -1 (invalid position), floats with
    zero — the empty-row convention of ``init_cache``."""
    fill = -1 if jnp.issubdtype(a.dtype, jnp.integer) else 0
    return jnp.full((a.shape[0], lanes) + a.shape[2:], fill, a.dtype)


# ---------------------------------------------------------------------------
# components
# ---------------------------------------------------------------------------

class DenseAttnState:
    """N-row dense attention rows (global caches and sliding-window rings).

    Leaves are ``(stack, n_rows, Sc, ...)``; rows fork by copying, pack by
    slicing.  Token-packable only when every slot keeps the full sequence
    axis (``Sc == max_len``): sliding-window rings fold positions, so a
    windowed row cannot be reconstructed from token rows."""

    name = "dense-attn"

    def __init__(self, max_len: int):
        self.max_len = max_len

    @staticmethod
    def owns(slot_cache) -> bool:
        return isinstance(slot_cache, dict) and "k" in slot_cache

    def token_packable(self, cache) -> bool:
        return all(
            all(a.shape[2] == self.max_len for a in jax.tree.leaves(c))
            for c in iter_slots(cache) if self.owns(c))

    # one (L, width) float32 block per leaf, concatenated by DecodeState
    def pack_parts(self, cache, row: int, length: int) -> List[jax.Array]:
        parts = []
        for c in iter_slots(cache):
            if not self.owns(c):
                continue
            for lf in jax.tree.leaves(c):
                parts.append(jnp.moveaxis(lf[:, row, :length], 1, 0)
                             .reshape(length, -1).astype(jnp.float32))
        return parts

    def unpack_slot(self, c, row: int, rows: np.ndarray, off: int
                    ) -> Tuple[dict, int]:
        """Rebuild one slot's row from packed token rows; slots beyond
        ``len(rows)`` reset to empty."""
        L = rows.shape[0]
        leaves, treedef = jax.tree.flatten(c)
        out = []
        for lf in leaves:
            stack, tail = lf.shape[0], lf.shape[3:]
            width = stack * int(np.prod(tail, dtype=np.int64))
            seg = rows[:, off:off + width].reshape((L, stack) + tail)
            off += width
            dtype = np.dtype(lf.dtype)
            fill = -1 if np.issubdtype(dtype, np.integer) else 0
            full = np.full((stack, lf.shape[2]) + tail, fill, dtype)
            full[:, :L] = np.moveaxis(seg, 0, 1)
            out.append(lf.at[:, row].set(jnp.asarray(full)))
        return jax.tree.unflatten(treedef, out), off


class PagedAttnState:
    """Attention KV scattered across a ``PagedKVPool``'s pages.

    Leaves are ``(stack, num_pages + 1, KV, page_size, hd)`` — no batch axis;
    rows exist only as page-table views built per call from the pool
    (``bind`` attaches a pool stream to a decoder row).  Forks copy
    nothing (the pool's COW fork shares pages; a COW split is mirrored
    physically through ``copy_page``), rollback frees pages with zero data
    movement, and pack/unpack move a row straight through its table —
    partial tail page included — so preemption never densifies the cache."""

    name = "paged-attn"

    def __init__(self, pool: PagedKVPool, max_len: int):
        self.pool = pool
        self.n_table = pool.pages_for(max_len)
        self.trash = pool.num_pages
        self.row_key: Dict[int, Any] = {}

    @staticmethod
    def owns(slot_cache) -> bool:
        return isinstance(slot_cache, dict) and "k_pages" in slot_cache

    def bind(self, row: int, key: Any) -> None:
        self.row_key[row] = key

    def unbind(self, row: int) -> None:
        self.row_key.pop(row, None)

    def table_view(self, rows: Optional[Sequence[int]], n_rows: int
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """(table, lens) for a batched call: bound rows expose their pool
        stream's pages; unbound rows (and pad lanes, row < 0) are empty —
        lens 0, every write routed to the trash page, every read masked."""
        n = n_rows if rows is None else len(rows)
        tab = np.full((n, self.n_table), self.trash, np.int32)
        lens = np.zeros(n, np.int32)
        it = range(n_rows) if rows is None else rows
        for i, row in enumerate(it):
            key = self.row_key.get(row)
            if key is None or not self.pool.is_open(key):
                continue
            t = self.pool.table(key)
            tab[i, :len(t)] = t
            lens[i] = self.pool.length(key)
        return tab, lens

    def pack_parts(self, cache, row: int, length: int) -> List[jax.Array]:
        table = jnp.asarray(
            np.asarray(self.pool.table(self.row_key[row]), np.int64))
        parts = []
        for c in iter_slots(cache):
            if not self.owns(c):
                continue
            for lf in jax.tree.leaves(c):
                pg = lf[:, table]
                # (stack, n, KV, ps, hd) -> token-major (n*ps, stack*KV*hd)
                pg = jnp.swapaxes(pg, 2, 3)
                tok = jnp.moveaxis(
                    pg.reshape(pg.shape[0], -1, *pg.shape[3:]), 1, 0)
                parts.append(tok[:length].reshape(length, -1)
                             .astype(jnp.float32))
        return parts

    def unpack_slot(self, c, row: int, rows: np.ndarray, off: int
                    ) -> Tuple[dict, int]:
        """Scatter packed token rows into the pages of the row's (freshly
        re-extended) table; the stale tail of a partial last page stays
        masked by the row's pool length."""
        key = self.row_key[row]
        table = self.pool.table(key)
        L = rows.shape[0]
        assert self.pool.length(key) == L, (self.pool.length(key), L)
        ps = self.pool.page_size
        n = len(table)
        leaves, treedef = jax.tree.flatten(c)
        out = []
        for lf in leaves:
            # head-major leaves (stack, P + 1, KV, ps, hd); rows are
            # token-major (L, stack*KV*hd) as pack_parts wrote them
            stack, tail = lf.shape[0], (lf.shape[2], lf.shape[4])
            width = stack * int(np.prod(tail, dtype=np.int64))
            seg = rows[:, off:off + width].reshape((L, stack) + tail)
            off += width
            pad = n * ps - L
            if pad:
                seg = np.concatenate(
                    [seg, np.zeros((pad, stack) + tail, seg.dtype)])
            pages = seg.reshape((n, ps, stack) + tail).transpose(2, 0, 3,
                                                                 1, 4)
            out.append(lf.at[:, jnp.asarray(table)].set(
                jnp.asarray(pages, lf.dtype)))
        return jax.tree.unflatten(treedef, out), off


class SSMRingState:
    """Per-row position-indexed checkpoint rings for recurrent slots
    (DESIGN.md §7.6).

    Leaves are ``(stack, n_rows, ring, ...)``; slot ``k % ring`` holds the
    post-step carry after the row's k-th token, so rollback is the same
    positional reset as attention.  Rings are state, not token rows — they
    never pack; a preempted row's ring instead survives as ONE explicit
    checkpoint (``snapshot``/``restore`` at the packed length)."""

    name = "ssm-ring"

    def __init__(self, ring: int):
        assert ring > 0
        self.ring = ring

    @staticmethod
    def owns(slot_cache) -> bool:
        return isinstance(slot_cache, dict) and "h_ring" in slot_cache

    def slots(self, cache) -> List[dict]:
        return [c for c in iter_slots(cache) if self.owns(c)]

    def snapshot_flat(self, cache, row: int, step: int) -> jax.Array:
        """One row's recurrent state at stream length ``step``, flattened
        and concatenated on device so the host copy crosses the boundary
        in ONE transfer."""
        s = step % self.ring
        return jnp.concatenate(
            [jnp.concatenate([c["h_ring"][:, row, s].reshape(-1)
                              .astype(jnp.float32),
                              c["conv_ring"][:, row, s].reshape(-1)
                              .astype(jnp.float32)])
             for c in self.slots(cache)])

    def snapshot_split(self, cache, buf: np.ndarray
                       ) -> List[Dict[str, np.ndarray]]:
        """Split a fetched ``snapshot_flat`` buffer back into one {h, conv}
        dict per recurrent slot."""
        out, off = [], 0
        for c in self.slots(cache):
            h_shape = (c["h_ring"].shape[0],) + c["h_ring"].shape[3:]
            c_shape = (c["conv_ring"].shape[0],) + c["conv_ring"].shape[3:]
            hn = int(np.prod(h_shape))
            cn = int(np.prod(c_shape))
            out.append({
                "h": buf[off:off + hn].reshape(h_shape),
                "conv": buf[off + hn:off + hn + cn].reshape(c_shape)
                .astype(c["conv_ring"].dtype),
            })
            off += hn + cn
        return out

    def restore(self, cache, row: int, step: int,
                snap: List[Dict[str, np.ndarray]]):
        """Write a snapshot back into the ring at ``step`` — after which a
        forward starting at position ``step`` resumes from it."""
        s = step % self.ring
        it = iter(snap)

        def put(c):
            if self.owns(c):
                sn = next(it)
                return dict(
                    c,
                    h_ring=c["h_ring"].at[:, row, s].set(
                        jnp.asarray(sn["h"])),
                    conv_ring=c["conv_ring"].at[:, row, s].set(
                        jnp.asarray(sn["conv"], c["conv_ring"].dtype)))
            return c

        return M.map_slot_caches(cache, put)


# ---------------------------------------------------------------------------
# composition
# ---------------------------------------------------------------------------

class DecodeState:
    """Per-row decode state assembled from storage components.

    Owns the cache pytree, the per-row write heads and the free-row list;
    every engine-facing state operation — fork, rollback, bind, swap
    pack/unpack, ring snapshot/restore — dispatches to the components, so
    the decoder and engines above never branch on the storage layout.
    """

    def __init__(self, cfg: ModelConfig, *, n_rows: int, max_len: int,
                 paged: Optional[PagedKVPool] = None, ssm_ring: int = 0,
                 mesh=None):
        self.cfg, self.n_rows, self.max_len = cfg, n_rows, max_len
        self.mesh = mesh
        self.ssm_ring = max(0, ssm_ring)
        has_ssm = any(m == "mamba" for m, _ in cfg.pattern)
        if has_ssm and self.ssm_ring <= 0:
            raise ValueError(
                "batched decoding of an SSM-bearing config needs a "
                "checkpoint ring (ssm_ring > 0) for per-row rollback")
        self.paged: Optional[PagedAttnState] = None
        self.ssm: Optional[SSMRingState] = None
        if paged is not None:
            # Sharded paged layout (DESIGN.md §7.10): the page axis stays
            # unsharded and KV heads split over "model", so one logical
            # page id p names the family of (device, p) per-head shards.
            # The host-side pool accounting (tables, refcounts, COW) is
            # device-agnostic and unchanged — every shard sees the same
            # replicated page table and reads/writes only its head slice.
            self.paged = PagedAttnState(paged, max_len)
            self.cache = self._init_cache(
                lambda: M.init_paged_cache(
                    cfg, paged.num_pages, paged.page_size,
                    n_rows=n_rows if has_ssm else 0,
                    ssm_ring=self.ssm_ring),
                batch_axis="")
            self.attn: Any = self.paged
        else:
            # dense rows shard their batch axis over "data" (degrading to
            # replication when the row count doesn't divide)
            self.cache = self._init_cache(
                lambda: M.init_cache(cfg, n_rows, max_len,
                                     ssm_ring=self.ssm_ring),
                batch_axis="data")
            self.attn = DenseAttnState(max_len)
        if has_ssm:
            self.ssm = SSMRingState(self.ssm_ring)

        self.free_rows: List[int] = list(range(n_rows - 1, -1, -1))
        # per-row write head: idle rows in a batched call park HERE, so
        # their pad writes land exactly where the row's next real write
        # lands (causally masked until overwritten) — parking anywhere
        # else would clobber live slots (pos 0 = the first prompt token!)
        # (In paged mode any write at a position >= the row's pool length
        # is routed to the trash page instead, same masking guarantee.)
        self.row_pos = np.zeros(n_rows, np.int64)

        # swap layout: the attention half of a row flattens to (L,
        # swap_dim) float32 token rows (per token each leaf contributes
        # stack * trailing dims); recurrent rings ride snapshot/restore.
        # paged leaves are head-major (stack, P + 1, KV, ps, hd): a token
        # carries KV * hd of each; dense leaves carry their trailing dims
        def tok_width(a):
            tail = ((a.shape[2], a.shape[4]) if self.paged is not None
                    else a.shape[3:])
            return a.shape[0] * int(np.prod(tail, dtype=np.int64))

        self.swap_dim = 0
        for c in iter_slots(self.cache):
            if self.attn.owns(c):
                self.swap_dim += sum(tok_width(a)
                                     for a in jax.tree.leaves(c))
        # token-packable attention + a recurrent half that can ride a ring
        # snapshot.  Dense hybrid stays UNswappable on purpose: the dense
        # backend is the reference oracle, and its preemption path (full
        # prefix recompute) is the baseline the paged swap is pinned
        # against (tests/test_hybrid_serving.py).
        if self.paged is not None:
            self.swappable = self.swap_dim > 0
        else:
            self.swappable = (self.ssm is None and self.swap_dim > 0
                              and self.attn.token_packable(self.cache))

        paged_owns = PagedAttnState.owns
        # does any slot carry a row axis (dense KV, rings)?  Pure-paged
        # configs have none: a fork is pure page-table sharing and must
        # stay a device no-op (the _copy_row jit would otherwise
        # materialize a fresh pool-sized buffer per branch fork).
        self._has_row_axis = any(not paged_owns(c)
                                 for c in iter_slots(self.cache))

        @functools.partial(jax.jit, donate_argnums=(0,))
        def _copy_row(cache, src, dst):
            """Row fork: every row-axis component copies its row in place
            (donated buffers); paged slots pass through untouched — the
            fork is page-table sharing in the pool."""
            def cp_slot(c):
                if paged_owns(c):
                    return c

                def cp(a):
                    r = jax.lax.dynamic_slice_in_dim(a, src, 1, axis=1)
                    return jax.lax.dynamic_update_slice_in_dim(a, r, dst,
                                                               axis=1)
                return jax.tree.map(cp, c)
            return M.map_slot_caches(cache, cp_slot)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def _copy_page(cache, src, dst):
            """Physical COW mirror: duplicate one page in every paged
            leaf (page axis = 1, after the layer-stack axis)."""
            def cp_slot(c):
                if not paged_owns(c):
                    return c

                def cp(a):
                    r = jax.lax.dynamic_slice_in_dim(a, src, 1, axis=1)
                    return jax.lax.dynamic_update_slice_in_dim(a, r, dst,
                                                               axis=1)
                return jax.tree.map(cp, c)
            return M.map_slot_caches(cache, cp_slot)

        self._copy_row_fn = _copy_row
        self._copy_page_fn = _copy_page

    def _init_cache(self, init, *, batch_axis: str):
        """Build the cache pytree, created directly under its mesh
        shardings when a mesh is set (``jit`` + ``out_shardings``, so big
        pools never materialize unsharded on one device)."""
        if self.mesh is None:
            return init()
        specs = _rules.serving_cache_specs(
            self.mesh, self.cfg, jax.eval_shape(init),
            batch_axis=batch_axis)
        return jax.jit(init,
                       out_shardings=_rules.named(self.mesh, specs))()

    # --------------------------------------------------------------- rows
    @property
    def has_ssm(self) -> bool:
        return self.ssm is not None

    def alloc(self) -> int:
        return self.free_rows.pop()

    def free(self, row: int) -> None:
        self.free_rows.append(row)

    def rollback(self, row: int, pos: int) -> None:
        """Positional rollback: park the write head at the new logical
        length.  No data moves — attention masks stale slots causally,
        pools reclaim pages (caller-side accounting), rings resume from
        the ``pos`` checkpoint."""
        self.row_pos[row] = pos

    def fork(self, src: int, dst: int) -> None:
        """COW fork of one row: row-axis state copies, paged state shares
        (the caller forks the pool stream and binds ``dst``).  With no
        row-axis slots (pure paged attention) the fork moves zero bytes."""
        if self._has_row_axis:
            self.cache = self._copy_row_fn(self.cache, jnp.int32(src),
                                           jnp.int32(dst))
        self.row_pos[dst] = self.row_pos[src]

    # -------------------------------------------------------------- paged
    def bind(self, row: int, key: Any) -> None:
        if self.paged is not None:
            self.paged.bind(row, key)

    def unbind(self, row: int) -> None:
        if self.paged is not None:
            self.paged.unbind(row)

    def copy_page(self, src: int, dst: int) -> None:
        self.cache = self._copy_page_fn(self.cache, jnp.int32(src),
                                        jnp.int32(dst))

    def table_view(self, rows: Optional[Sequence[int]] = None
                   ) -> Tuple[np.ndarray, np.ndarray]:
        assert self.paged is not None
        return self.paged.table_view(rows, self.n_rows)

    # ------------------------------------------------------------ prefill
    def prefill_view(self, cache, lanes: int):
        """Batch-``lanes`` cache view for a bucketed prefill forward
        (traced inside the decoder's jit): paged slots pass through (pages
        are shared storage — fresh rows write straight into them through
        their tables), row-axis slots are replaced by fresh ``lanes``-row
        buffers (prefill targets FRESH rows only, so nothing is
        gathered)."""
        paged_owns = PagedAttnState.owns

        def fix(c):
            if paged_owns(c):
                return c
            return jax.tree.map(lambda a: _fresh_like(a, lanes), c)
        return M.map_slot_caches(cache, fix)

    def prefill_take(self, cache, rows: jax.Array):
        """Batch-``lanes`` cache view for a SUFFIX prefill forward (traced
        inside the decoder's jit): like ``prefill_view`` but row-axis
        slots GATHER lane i from ``rows[i]`` instead of starting fresh — a
        prefix-cache hit restores the run's ring checkpoint into the live
        row *before* the suffix forward, and the gathered view carries it
        into the call (a fresh view would zero it).  Pad lanes carry an
        out-of-bounds row id: the gather clamps them to junk that
        ``prefill_merge``'s scatter drops."""
        paged_owns = PagedAttnState.owns

        def fix(c):
            if paged_owns(c):
                return c
            return jax.tree.map(lambda a: a[:, rows], c)
        return M.map_slot_caches(cache, fix)

    def prefill_merge(self, cache, sub, rows: jax.Array):
        """Merge a prefill forward's ``lanes``-batch result back (traced
        inside the decoder's jit): paged slots adopt the written pages,
        row-axis slots scatter lane i to ``rows[i]`` (pad lanes carry an
        out-of-bounds row id and are dropped by the scatter)."""
        paged_owns = PagedAttnState.owns

        def fix(c, s):
            if paged_owns(c):
                return s
            return jax.tree.map(
                lambda a, b: a.at[:, rows].set(b.astype(a.dtype)), c, s)
        return {"blocks": [fix(c, s) for c, s in
                           zip(cache["blocks"], sub["blocks"])],
                "rem": [fix(c, s) for c, s in
                        zip(cache["rem"], sub["rem"])]}

    # --------------------------------------------------------------- swap
    def pack_row(self, row: int, length: int) -> jax.Array:
        """Flatten the attention half of a row's first ``length`` slots to
        (L, swap_dim) float32 token rows ON DEVICE (one concatenated
        array; the caller fetches it in one transfer).  Recurrent rings
        are position-indexed state, not token rows — they ride
        ``snapshot``/``restore``."""
        assert self.swappable
        parts = self.attn.pack_parts(self.cache, row, length)
        return jnp.concatenate(parts, axis=1)

    def unpack_row(self, row: int, rows: np.ndarray) -> None:
        """Restore a row's attention state from packed token rows (inverse
        of ``pack_row``); dense slots beyond len(rows) reset to empty."""
        assert self.swappable
        off = 0
        out = []
        for c in iter_slots(self.cache):
            if self.attn.owns(c):
                c, off = self.attn.unpack_slot(c, row, rows, off)
            out.append(c)
        n_blocks = len(self.cache["blocks"])
        self.cache = {"blocks": out[:n_blocks], "rem": out[n_blocks:]}
        self.row_pos[row] = rows.shape[0]

    # ---------------------------------------------------------- ssm rings
    def snapshot_flat(self, row: int, step: int) -> jax.Array:
        assert self.ssm is not None, "snapshot needs a checkpoint-ring cache"
        return self.ssm.snapshot_flat(self.cache, row, step)

    def snapshot_split(self, buf: np.ndarray) -> List[Dict[str, np.ndarray]]:
        assert self.ssm is not None
        return self.ssm.snapshot_split(self.cache, buf)

    def restore(self, row: int, step: int,
                snap: List[Dict[str, np.ndarray]]) -> None:
        assert self.ssm is not None
        self.cache = self.ssm.restore(self.cache, row, step, snap)
