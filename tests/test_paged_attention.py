"""Paged-attention decode kernel + physically paged serving path.

Three layers of evidence that paged execution is lossless:

  * kernel vs oracle — shape/feature sweep against the dense gather
    reference (ref.paged_attention_ref);
  * property test — randomly fragmented page tables with random per-row
    sequence lengths, including shared-prefix COW forks, must match dense
    ``flash_attention`` over the gathered rows;
  * engine equivalence — batched SpS/SpecBranch serving with
    ``attn_backend="paged"`` must emit token-for-token the streams of the
    dense backend (greedy AND sampled), through branch forks, rollbacks
    and preemption.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.models import model as M
from repro.models.config import ModelConfig, dense_pattern
from repro.runtime.engines import EngineConfig
from repro.runtime.runner import greedy_reference
from repro.serving import (BatchedSpecBranchEngine, BatchedSpSEngine,
                           ContinuousBatchScheduler, ServeRequest)

KEY = jax.random.PRNGKey(11)
N_NEW = 8
VOCAB = 64


# ---------------------------------------------------------------------------
# kernel vs oracle
# ---------------------------------------------------------------------------

def _layout(rng, lens, ps, n_phys=None):
    """Random fragmented page tables for given per-row lengths; trash page
    is the last physical page, tables pad with it."""
    n_pages = [-(-ln // ps) for ln in lens]
    total = sum(n_pages)
    P = total if n_phys is None else n_phys
    assert P >= total
    table = np.full((len(lens), max(max(n_pages), 1)), P, np.int32)
    perm = rng.permutation(P)
    off = 0
    for b, npg in enumerate(n_pages):
        table[b, :npg] = perm[off:off + npg]
        off += npg
    return table, P


@pytest.mark.parametrize("B,T,H,KV,hd,ps,variant", [
    (1, 1, 4, 4, 32, 8, "causal"),       # MHA single-token decode
    (3, 5, 4, 2, 16, 8, "causal"),       # GQA multi-token verify chunk
    (2, 7, 8, 2, 64, 16, "window"),      # sliding-window local layer
    (2, 3, 6, 3, 32, 4, "cap"),          # logit softcap, tiny pages
])
def test_paged_attention_vs_oracle(B, T, H, KV, hd, ps, variant):
    rng = np.random.default_rng(5)
    lens = [int(rng.integers(T + 1, 6 * ps)) for _ in range(B)]
    table, P = _layout(rng, lens, ps)
    kp = jnp.asarray(rng.normal(size=(P + 1, KV, ps, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P + 1, KV, ps, hd)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, T, H, hd)), jnp.float32)
    lens = np.asarray(lens, np.int32)
    q_start = lens - T
    kw = {"window": 5} if variant == "window" else \
         {"cap": 20.0} if variant == "cap" else {}
    out = ops.paged_attention(q, kp, vp, table, lens, q_start, **kw)
    want = ref.paged_attention_ref(q, kp, vp, jnp.asarray(table),
                                   jnp.asarray(lens),
                                   jnp.asarray(q_start), **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_paged_attention_zero_length_rows():
    """Unbound decoder rows attend over nothing: lens 0 must not NaN."""
    rng = np.random.default_rng(9)
    kp = jnp.asarray(rng.normal(size=(4, 2, 8, 16)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(4, 2, 8, 16)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(2, 2, 4, 16)), jnp.float32)
    table = np.asarray([[0, 1], [3, 3]], np.int32)
    out = ops.paged_attention(q, kp, vp, table,
                              np.asarray([10, 0], np.int32),
                              np.asarray([8, 0], np.int32))
    assert np.isfinite(np.asarray(out)).all()
    assert np.abs(np.asarray(out)[1]).max() == 0.0


# ---------------------------------------------------------------------------
# property: fragmented tables == dense flash attention (incl. COW forks)
# ---------------------------------------------------------------------------

@given(st.integers(0, 2**31 - 1))
@settings(max_examples=8, deadline=None)
def test_paged_matches_dense_on_fragmented_tables(seed):
    """Random fragmentation, random ragged lens, and a shared-prefix COW
    fork pair: paged attention over the scattered pages must match dense
    flash attention over the gathered rows."""
    rng = np.random.default_rng(seed)
    ps = int(rng.choice([4, 8]))
    KV, hd = 2, 16
    H = KV * int(rng.choice([1, 2]))
    T = int(rng.integers(1, 5))
    B = int(rng.integers(2, 5))
    lens = [int(rng.integers(T + 1, 5 * ps)) for _ in range(B)]
    n_pages = [-(-ln // ps) for ln in lens]
    P = sum(n_pages) + 2
    table, _ = _layout(rng, lens, ps, n_phys=P)

    # rows 0/1 become a COW fork: identical prefix pages, private tails
    fork = min(n_pages[0], n_pages[1])
    if fork > 1:
        table[1, :fork - 1] = table[0, :fork - 1]
    kp = jnp.asarray(rng.normal(size=(P + 1, KV, ps, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P + 1, KV, ps, hd)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, T, H, hd)), jnp.float32)
    lens = np.asarray(lens, np.int32)
    q_start = lens - T
    out = ops.paged_attention(q, kp, vp, table, lens, q_start)

    smax = table.shape[1] * ps
    dense_k = np.asarray(kp)[table].transpose(0, 1, 3, 2, 4).reshape(
        B, smax, KV, hd)
    dense_v = np.asarray(vp)[table].transpose(0, 1, 3, 2, 4).reshape(
        B, smax, KV, hd)
    kpos = np.where(np.arange(smax)[None] < lens[:, None],
                    np.arange(smax)[None], -1)
    qpos = q_start[:, None] + np.arange(T)[None]
    want = ops.flash_attention(q, jnp.asarray(dense_k),
                               jnp.asarray(dense_v), jnp.asarray(qpos),
                               jnp.asarray(kpos), bq=8, bk=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# engine equivalence: paged backend == dense backend, token for token
# ---------------------------------------------------------------------------

def _cfg(name, layers, d, heads):
    return ModelConfig(name=name, family="dense", num_layers=layers,
                       d_model=d, num_heads=heads,
                       num_kv_heads=max(1, heads // 2), d_ff=4 * d,
                       vocab_size=VOCAB, pattern=dense_pattern(0),
                       dtype="float32")


def _ecfg(**kw):
    kw.setdefault("gamma", 3)
    kw.setdefault("c", 4.0)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("epsilon", 0.4)
    kw.setdefault("signal_temperature", 0.5)
    kw.setdefault("k_max", 2)
    kw.setdefault("max_len", 128)
    return EngineConfig(**kw)


@pytest.fixture(scope="module")
def pair():
    tcfg = _cfg("paged-t", 2, 64, 2)
    dcfg = _cfg("paged-d", 1, 32, 2)
    tp = M.init_params(jax.random.PRNGKey(0), tcfg)
    dp = M.init_params(jax.random.PRNGKey(1), dcfg)
    rng = np.random.default_rng(3)
    prompts = [list(map(int, rng.integers(0, VOCAB, size=6)))
               for _ in range(3)]
    return dp, dcfg, tp, tcfg, prompts


def _serve(pair_, cls, backend, n_req=2, **ekw):
    dp, dcfg, tp, tcfg, prompts = pair_
    eng = cls(dp, dcfg, tp, tcfg, _ecfg(**ekw.pop("ecfg", {})),
              max_batch=n_req, page_size=4, attn_backend=backend,
              debug_check=True, **ekw)
    res = ContinuousBatchScheduler(eng).run(
        [ServeRequest(rid=i, prompt=p, max_new_tokens=N_NEW)
         for i, p in enumerate(pair_[4][:n_req])])
    return eng, res


@pytest.mark.parametrize("cls", [BatchedSpSEngine, BatchedSpecBranchEngine])
def test_paged_backend_greedy_lossless(pair, cls):
    """Paged serving == the AR reference (and hence == the dense backend,
    which the serving suite already pins to the same reference)."""
    dp, dcfg, tp, tcfg, prompts = pair
    refs = [greedy_reference(tp, tcfg, p, N_NEW, max_len=128)
            for p in prompts[:2]]
    eng, res = _serve(pair, cls, "paged")
    for i, want in enumerate(refs):
        assert res[i].tokens == want, i
    assert eng.pool.pages_in_use == 0
    eng.pool.check()


def test_paged_equals_dense_at_temperature_one(pair):
    """Sampled streams (temp 1) must be identical across backends: the
    host-side per-request RNG sees the same logits only if paged attention
    is numerically faithful through forks, adoptions and rollbacks."""
    outs = {}
    for backend in ("dense", "paged"):
        _, res = _serve(pair, BatchedSpecBranchEngine, backend,
                        ecfg={"temperature": 1.0})
        outs[backend] = {i: r.tokens for i, r in res.items()}
    assert outs["dense"] == outs["paged"]


def test_split_pools_halve_paged_buffers(pair):
    """Split page-id spaces (DESIGN.md §7.6 follow-up to PR 2): each
    physically paged decoder sizes its buffers to ITS OWN pool, so the
    per-decoder physical footprint drops from pool-wide (t+d pages, the
    old shared id space) to its split share."""
    eng, _ = _serve(pair, BatchedSpSEngine, "paged")
    t_pages = eng.pools["t"].num_pages
    d_pages = eng.pools["d"].num_pages
    assert eng.pool.num_pages == t_pages + d_pages
    for dec, own in ((eng.tgt_dec, t_pages), (eng.dft_dec, d_pages)):
        for leaf in jax.tree_util.tree_leaves(dec.cache):
            # page axis sized to the decoder's own pool (+1 trash page),
            # strictly smaller than the old shared-pool sizing
            assert leaf.shape[1] == own + 1
            assert leaf.shape[1] < t_pages + d_pages + 1
    # regression: the shared id space made each buffer (t+d)+1 pages; the
    # split totals exactly the old SINGLE decoder's footprint across BOTH
    assert (eng.tgt_dec.cache["blocks"][0]["k_pages"].shape[1]
            + eng.dft_dec.cache["blocks"][0]["k_pages"].shape[1]
            == eng.pool.num_pages + 2)


def test_paged_swap_roundtrip_partial_tail_page(pair):
    """pack_row/unpack_row on the paged backend move a row's KV straight
    through its page table — including a PARTIAL tail page — and restore
    it into a different physical layout exactly."""
    from repro.serving.batched_engine import BatchedDecoder
    from repro.serving.kv_pool import PagedKVPool
    dp, dcfg, tp, tcfg, prompts = pair
    pool = PagedKVPool(16, 4)
    dec = BatchedDecoder(tp, tcfg, n_rows=2, max_len=64, paged=pool)
    pool.cow_listeners.append(dec.copy_page)
    prompt = prompts[0] + prompts[1][:1]          # len 7: 4 + partial 3
    assert len(prompt) % pool.page_size != 0
    row = dec.free_rows.pop()
    pool.open("s")
    pool.extend("s", len(prompt))
    dec.bind_row(row, "s")
    dec.prefill_row(row, prompt)
    packed = dec.pack_row(row, len(prompt))
    assert packed.shape == (len(prompt), dec.swap_dim)

    # decode one step from the original layout
    tok = np.zeros((2, 1), np.int32)
    pos = np.zeros((2,), np.int32)
    tok[row, 0], pos[row] = 5, len(prompt)
    pool.extend("s", 1)
    ref_lg, _ = dec.step(tok.copy(), pos.copy())
    ref = np.asarray(ref_lg)[row]

    # drop the stream (pages go back fragmented), reopen at a DIFFERENT
    # physical layout, unpack, decode again: logits must match exactly
    pool.close("s", "preempt")
    dec.unbind_row(row)
    pool.open("pad")                              # shift the free list
    pool.extend("pad", 5)
    pool.open("s2")
    pool.extend("s2", len(prompt))
    dec.bind_row(row, "s2")
    dec.unpack_row(row, packed)
    pool.extend("s2", 1)
    got_lg, _ = dec.step(tok, pos)
    np.testing.assert_allclose(np.asarray(got_lg)[row], ref,
                               rtol=1e-5, atol=1e-5)


def test_paged_backend_cow_forks_share_pages(pair):
    """Branch forks on the paged backend must COW-share (fork allocates
    zero pages; diverging branches split tails) and reclaim losers."""
    eng, _ = _serve(pair, BatchedSpecBranchEngine, "paged")
    st_ = eng.pool.stats
    assert st_.cow_copies > 0
    assert st_.reclaimed_speculative_pages > 0
    assert eng.pool.pages_in_use == 0


@pytest.mark.parametrize("swap_pages", [0, 64])
def test_paged_backend_preemption_exact(pair, swap_pages):
    """Pool pressure: preempt, re-admit, still token-exact — with the
    paged swap store (rows packed/unpacked straight from pages) and
    without (prefix recompute)."""
    dp, dcfg, tp, tcfg, prompts = pair
    refs = [greedy_reference(tp, tcfg, p, N_NEW, max_len=128)
            for p in prompts]
    eng = BatchedSpecBranchEngine(dp, dcfg, tp, tcfg, _ecfg(),
                                  max_batch=3, page_size=2, pool_pages=40,
                                  swap_pages=swap_pages,
                                  attn_backend="paged", debug_check=True)
    assert eng.tgt_dec.swappable       # pages pack without densifying
    assert (eng.swap is not None) == bool(swap_pages)
    sched = ContinuousBatchScheduler(eng)
    res = sched.run([ServeRequest(rid=i, prompt=p, max_new_tokens=N_NEW)
                     for i, p in enumerate(prompts)])
    assert sched.metrics.preemptions > 0
    for i, want in enumerate(refs):
        assert res[i].tokens == want, i
    assert eng.pool.pages_in_use == 0
    if swap_pages:
        assert eng.swap.pool.pages_in_use == 0
