"""Pallas kernel validation: shape/dtype sweeps, assert_allclose vs ref.py
oracles (interpret mode executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: dict(rtol=2e-4, atol=2e-4),
       jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,T,H,KV,hd,S", [
    (1, 8, 4, 4, 32, 8),       # MHA square
    (2, 17, 8, 2, 64, 33),     # GQA, ragged vs blocks
    (1, 1, 4, 4, 128, 40),     # single-token decode
    (3, 5, 6, 3, 16, 70),      # odd head group
])
@pytest.mark.parametrize("variant", ["causal", "window", "cap", "bidir"])
def test_flash_attention_sweep(dtype, B, T, H, KV, hd, S, variant):
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (B, T, H, hd), dtype)
    k = _rand(ks[1], (B, S, KV, hd), dtype)
    v = _rand(ks[2], (B, S, KV, hd), dtype)
    qp = jnp.broadcast_to(jnp.arange(S - T, S), (B, T))
    kp = jnp.where(jnp.arange(S) < S - 2, jnp.arange(S), -1)[None] \
        .repeat(B, 0)
    kw = dict(causal=True)
    if variant == "window":
        kw = dict(causal=True, window=7)
    elif variant == "cap":
        kw = dict(causal=True, cap=30.0)
    elif variant == "bidir":
        kw = dict(causal=False)
    out = ops.flash_attention(q, k, v, qp, kp, bq=16, bk=16, **kw)
    want = ref.attention_ref(q, k, v, qp, kp, **kw)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("kb,Sp,Ss,KV,hd", [
    (2, 16, 4, 2, 32),
    (4, 33, 7, 4, 64),
    (6, 8, 1, 1, 16),
])
def test_branch_decode_shared_prefix(kb, Sp, Ss, KV, hd):
    H = KV * 2
    ks = jax.random.split(KEY, 7)
    pk = _rand(ks[0], (1, Sp, KV, hd), jnp.float32)
    pv = _rand(ks[1], (1, Sp, KV, hd), jnp.float32)
    sk = _rand(ks[2], (kb, Ss, KV, hd), jnp.float32)
    sv = _rand(ks[3], (kb, Ss, KV, hd), jnp.float32)
    q = _rand(ks[4], (kb, 1, H, hd), jnp.float32)
    ppos = jnp.arange(Sp)[None]
    spos = jnp.broadcast_to(jnp.arange(Sp, Sp + Ss), (kb, Ss))
    qpos = jnp.full((kb, 1), Sp + Ss)
    out = ops.branch_decode_attention(q, pk, pv, ppos, sk, sv, spos, qpos)
    want = ref.branch_decode_ref(q, pk, pv, ppos, sk, sv, spos, qpos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,T,E,N", [
    (1, 7, 16, 4),
    (2, 40, 48, 16),
    (1, 130, 32, 8),      # multiple chunks with padding
])
def test_ssm_scan_sweep(dtype, B, T, E, N):
    ks = jax.random.split(KEY, 6)
    x = _rand(ks[0], (B, T, E), dtype)
    dt = jax.nn.softplus(_rand(ks[1], (B, T, E), jnp.float32)).astype(dtype)
    Bm = _rand(ks[2], (B, T, N), dtype)
    Cm = _rand(ks[3], (B, T, N), dtype)
    A = -jnp.exp(jax.random.normal(ks[4], (E, N)) * 0.2)
    D = jnp.ones((E,))
    h0 = jax.random.normal(ks[5], (B, E, N))
    y, hT = ops.ssm_scan(x, dt, Bm, Cm, A, D, h0, bT=16, bE=16)
    yr, hTr = ref.ssm_scan_ref(x, dt, Bm, Cm, A, D, h0)
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), **tol)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hTr), **tol)


def test_ssm_state_carry_across_chunks():
    """Chunked kernel must thread state across chunk boundaries exactly."""
    B, T, E, N = 1, 64, 8, 4
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (B, T, E))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, E)))
    Bm = jax.random.normal(ks[2], (B, T, N))
    Cm = jax.random.normal(ks[3], (B, T, N))
    A = -jnp.exp(jax.random.normal(ks[4], (E, N)) * 0.2)
    D = jnp.zeros((E,))
    h0 = jnp.zeros((B, E, N))
    y_small, _ = ops.ssm_scan(x, dt, Bm, Cm, A, D, h0, bT=8, bE=8)
    y_big, _ = ops.ssm_scan(x, dt, Bm, Cm, A, D, h0, bT=64, bE=8)
    np.testing.assert_allclose(np.asarray(y_small), np.asarray(y_big),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("R,V", [(1, 32), (5, 211), (9, 1024)])
def test_verify_accept_sweep(R, V):
    ks = jax.random.split(KEY, 5)
    p = jax.random.normal(ks[0], (R, V)) * 2
    q = jax.random.normal(ks[1], (R, V)) * 2
    toks = jax.random.randint(ks[2], (R,), 0, V)
    u = jax.random.uniform(ks[3], (R,))
    w = jax.random.uniform(ks[4], (R,))
    got = ops.verify_accept(p, q, toks, u, w)
    want = ref.verify_accept_ref(p, q, toks, u, w)
    for g, wv in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wv),
                                   rtol=1e-5, atol=1e-6)


def test_verify_accept_residual_is_distribution():
    """Residual samples must land on tokens where p > q (the residual's
    support), whenever that support is non-empty."""
    R, V = 64, 50
    ks = jax.random.split(KEY, 4)
    p = jax.random.normal(ks[0], (R, V)) * 3
    q = jax.random.normal(ks[1], (R, V)) * 3
    toks = jnp.zeros((R,), jnp.int32)
    u = jnp.zeros((R,))
    w = jax.random.uniform(ks[2], (R,))
    _, res, _, _ = ops.verify_accept(p, q, toks, u, w)
    pp = jax.nn.softmax(p, -1)
    qq = jax.nn.softmax(q, -1)
    sup = (pp - qq > 0)
    idx = np.arange(R)
    assert bool(sup[idx, np.asarray(res)].all())


@pytest.mark.parametrize("n,ps,dim", [(1, 4, 8), (5, 8, 16), (3, 16, 24),
                                      (2, 4, 3 * 8192)])  # 3 dim blocks
def test_paged_gather_matches_numpy(n, ps, dim):
    """Paged gather through a scalar-prefetched page table == buf[table]."""
    rng = np.random.default_rng(7)
    P = 11
    buf = rng.normal(size=(P, ps, dim)).astype(np.float32)
    table = rng.choice(P, size=n, replace=False).astype(np.int32)
    got = np.asarray(ops.paged_gather(buf, table))
    np.testing.assert_array_equal(got, buf[table].reshape(n * ps, dim))


def test_paged_gather_valid_len_zeroes_stale_tail():
    """Regression: the free list recycles pages without scrubbing, so a
    partially-filled last page still holds its previous owner's rows.
    A gather with valid_len must return zeros there — cache-restore after
    preemption must not resurrect a stale stream's KV."""
    rng = np.random.default_rng(11)
    buf = rng.normal(size=(8, 4, 8)).astype(np.float32)   # all pages dirty
    table = np.asarray([6, 3, 0], np.int32)
    L = 9                                  # last page only 1/4 filled
    got = np.asarray(ops.paged_gather(buf, table, L))
    want = buf[table].reshape(12, 8)
    np.testing.assert_array_equal(got[:L], want[:L])
    assert (got[L:] == 0).all(), "stale rows leaked past valid_len"
    # default (no valid_len) keeps the historical full-page behaviour
    np.testing.assert_array_equal(np.asarray(ops.paged_gather(buf, table)),
                                  want)


def test_paged_gather_repeated_pages():
    """Shared (COW) pages may appear in several tables — and in one table
    twice; the gather must not assume uniqueness."""
    rng = np.random.default_rng(8)
    buf = rng.normal(size=(6, 4, 8)).astype(np.float32)
    table = np.asarray([2, 2, 5, 2], np.int32)
    got = np.asarray(ops.paged_gather(buf, table))
    np.testing.assert_array_equal(got, buf[table].reshape(16, 8))
