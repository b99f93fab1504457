"""Per-kernel interpret-mode validation against the ref.py jnp oracles,
sweeping shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.types import QueryBatch, StoreView


def _rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# lsh_hash
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,d,k", [(128, 64, 8), (256, 100, 16),
                                   (130, 50, 12), (64, 32, 130)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lsh_hash_matches_ref(n, d, k, dtype):
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    x = _rand(k1, (n, d), dtype)
    a = _rand(k2, (d, k))
    b = jax.random.uniform(k3, (k,), maxval=0.5)
    got = ops.lsh_hash(x, a, b, w=0.5)
    want = ref.lsh_hash_ref(x, a, b, w=0.5)
    # floor() can differ when the projection lands within float eps of an
    # integer; everything else must agree exactly.
    agree = np.mean(np.asarray(got) == np.asarray(want))
    assert agree >= 0.999, agree
    assert np.max(np.abs(np.asarray(got) - np.asarray(want))) <= 1


def test_lsh_hash_multi_table_packing():
    """K > 128 exercises multiple lane tiles (many tables at once)."""
    key = jax.random.PRNGKey(7)
    x = _rand(key, (128, 40))
    a = _rand(jax.random.PRNGKey(8), (40, 256))
    b = jnp.zeros((256,))
    got = ops.lsh_hash(x, a, b, w=1.0)
    want = ref.lsh_hash_ref(x, a, b, w=1.0)
    assert np.mean(np.asarray(got) == np.asarray(want)) >= 0.999


# ---------------------------------------------------------------------------
# bucket_search
# ---------------------------------------------------------------------------

def _bucket_case(key, R, N, d, L, frac_match=0.2):
    ks = jax.random.split(key, 6)
    q = _rand(ks[0], (R, d))
    p = _rand(ks[1], (N, d))
    # small bucket universe so matches actually occur
    pbuckets = jax.random.randint(ks[2], (N, 2), 0, 16, dtype=jnp.int32)
    qbuckets = jax.random.randint(ks[3], (R, 2 * L), 0, 16, dtype=jnp.int32)
    probe = (jax.random.uniform(ks[4], (R, L)) < frac_match).astype(jnp.int32)
    pvalid = (jax.random.uniform(ks[5], (N,)) < 0.9).astype(jnp.int32)
    gid = jnp.arange(N, dtype=jnp.int32) * 3 + 1
    qsq = jnp.sum(q * q, axis=-1)
    psq = jnp.sum(p * p, axis=-1)
    return q, qsq, qbuckets, probe, p, psq, pbuckets, gid, pvalid


def _qs(args, qtable=None, ptable=None):
    """Wrap a `_bucket_case` 9-tuple in the typed kernel API."""
    q, qsq, qb, probe, p, psq, pb, gid, pv = args
    query = QueryBatch(
        q=q, qsq=qsq, buckets=qb, probe=probe,
        table=(qtable if qtable is not None
               else jnp.zeros((q.shape[0],), jnp.int32)))
    store = StoreView(
        points=p, psq=psq, buckets=pb, gid=gid, valid=pv,
        table=(ptable if ptable is not None
               else jnp.zeros((p.shape[0],), jnp.int32)))
    return query, store


@pytest.mark.parametrize("R,N,d,L", [(128, 128, 32, 4), (128, 256, 64, 8),
                                     (100, 200, 16, 2), (256, 384, 48, 16)])
def test_bucket_search_matches_ref(R, N, d, L):
    query, store = _qs(_bucket_case(jax.random.PRNGKey(R + N), R, N, d, L))
    cr2 = 2.5
    best_k, gid_k, cnt_k, _ = ops.bucket_search(query=query, store=store,
                                                cr2=cr2, L=L)
    best_r, gid_r, cnt_r = ref.bucket_search_ref(query=query, store=store,
                                                 cr2=cr2, L=L)
    np.testing.assert_allclose(np.asarray(best_k), np.asarray(best_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(cnt_k), np.asarray(cnt_r))
    # (dist, gid) lex order makes tie-breaks deterministic in both paths
    np.testing.assert_array_equal(np.asarray(gid_k), np.asarray(gid_r))


@pytest.mark.parametrize("K", [1, 5, 32])
@pytest.mark.parametrize("R,N,d,L", [(128, 384, 16, 8), (256, 256, 32, 4)])
def test_bucket_search_topk_matches_ref(K, R, N, d, L):
    """Top-K parity across point tiles, including rows with fewer than K
    hits (sentinel-padded tails must agree too)."""
    query, store = _qs(_bucket_case(jax.random.PRNGKey(K * 7 + R), R, N,
                                    d, L, frac_match=0.5))
    cr2 = 40.0  # wide threshold so most rows have many hits
    td_k, tg_k, c_k, _ = ops.bucket_search(query=query, store=store,
                                           cr2=cr2, L=L, k=K)
    td_r, tg_r, c_r = ref.bucket_search_ref(query=query, store=store,
                                            cr2=cr2, L=L, K=K)
    assert td_k.shape == (R, K) and tg_k.shape == (R, K)
    np.testing.assert_allclose(np.asarray(td_k), np.asarray(td_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(tg_k), np.asarray(tg_r))
    np.testing.assert_array_equal(np.asarray(c_k), np.asarray(c_r))
    # ascending (dist, gid) lex order with sentinel tails
    td = np.asarray(td_k)
    assert np.all(np.diff(td, axis=1) >= 0)
    short = np.asarray(c_r) < K
    if short.any():
        i = np.nonzero(short)[0][0]
        assert td[i, -1] == np.float32(np.finfo(np.float32).max)
        assert np.asarray(tg_k)[i, -1] == np.iinfo(np.int32).max


def test_bucket_search_topk_ties():
    """Duplicated points tie exactly on distance; the accumulator must
    order them by gid and not drop or double-count any."""
    R, N, L, K = 128, 256, 1, 5
    q = jnp.zeros((R, 8))
    p = jnp.tile(jnp.ones((1, 8)), (N, 1))      # all at distance sqrt(8)
    qb = jnp.zeros((R, 2), jnp.int32)
    pb = jnp.zeros((N, 2), jnp.int32)
    probe = jnp.ones((R, L), jnp.int32)
    pv = jnp.ones((N,), jnp.int32)
    gid = jnp.arange(N, dtype=jnp.int32)[::-1].copy()   # descending
    query, store = _qs((q, jnp.sum(q * q, -1), qb, probe, p,
                        jnp.sum(p * p, -1), pb, gid, pv))
    td_k, tg_k, cnt, _ = ops.bucket_search(query=query, store=store,
                                           cr2=100.0, L=L, k=K)
    td_r, tg_r, _ = ref.bucket_search_ref(query=query, store=store,
                                          cr2=100.0, L=L, K=K)
    np.testing.assert_array_equal(np.asarray(tg_k), np.asarray(tg_r))
    np.testing.assert_array_equal(np.asarray(tg_k)[0], np.arange(K))
    assert np.all(np.asarray(cnt) == N)


@pytest.mark.parametrize("T", [2, 4])
def test_bucket_search_table_mask(T):
    """Multi-table fusion: a stored row only matches probes of its own
    table.  Kernel == ref with table ids, and the T-table masked result
    equals running each table's rows separately."""
    R, N, d, L = 128, 256, 32, 4
    key = jax.random.PRNGKey(41 + T)
    args = _bucket_case(key, R, N, d, L, frac_match=0.6)
    ks = jax.random.split(jax.random.PRNGKey(99), 2)
    qtable = jax.random.randint(ks[0], (R,), 0, T, dtype=jnp.int32)
    ptable = jax.random.randint(ks[1], (N,), 0, T, dtype=jnp.int32)
    cr2 = 40.0
    query, store = _qs(args, qtable=qtable, ptable=ptable)
    td_k, tg_k, c_k, _ = ops.bucket_search(query=query, store=store,
                                           cr2=cr2, L=L, k=4)
    td_r, tg_r, c_r = ref.bucket_search_ref(query=query, store=store,
                                            cr2=cr2, L=L, K=4)
    np.testing.assert_allclose(np.asarray(td_k), np.asarray(td_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(tg_k), np.asarray(tg_r))
    np.testing.assert_array_equal(np.asarray(c_k), np.asarray(c_r))
    # per-table oracle: zero out the OTHER tables' stored rows via pvalid
    q, qsq, qb, probe, p, psq, pb, gid, pvalid = args
    for t in range(T):
        pv_t = jnp.asarray(pvalid * (np.asarray(ptable) == t))
        query0, store_t = _qs((q, qsq, qb, probe, p, psq, pb, gid, pv_t))
        td_t, tg_t, c_t = ref.bucket_search_ref(query=query0, store=store_t,
                                                cr2=cr2, L=L, K=4)
        rows = np.asarray(qtable) == t
        np.testing.assert_array_equal(np.asarray(tg_k)[rows],
                                      np.asarray(tg_t)[rows])
        np.testing.assert_array_equal(np.asarray(c_k)[rows],
                                      np.asarray(c_t)[rows])


def test_bucket_search_no_matches():
    R, N, d, L = 128, 128, 8, 2
    args = list(_bucket_case(jax.random.PRNGKey(0), R, N, d, L))
    args[3] = jnp.zeros_like(args[3])  # probe nothing
    query, store = _qs(tuple(args))
    best, gid, cnt, _ = ops.bucket_search(query=query, store=store,
                                          cr2=1.0, L=L, k=4)
    assert np.all(np.asarray(best) == np.float32(np.finfo(np.float32).max))
    assert np.all(np.asarray(gid) == np.iinfo(np.int32).max)
    assert np.all(np.asarray(cnt) == 0)


def _merge_case(case, K, R=256, N=640, d=8, span=200, seed=0):
    """Integer-valued rows, so every dist^2 is exact in f32 and both
    kernels and the oracle agree bit for bit.  The store is sorted into
    buckets of ``span`` rows (crossing tile edges); query row r probes
    bucket r % 4, so rows of the short last bucket see fewer hits.

      none      no pair within cr: every tile merges with zero passes
      tightens  dist^2 falls along the store, so each tile brings more
                improving pairs than the running K can hold and its
                K-th tightens inside the tile
      ties      two dist^2 levels shared by every row and point, gids
                falling along the store: tile pairs tie the accumulator's
                and enter by gid alone
      few       K - 1 valid rows per bucket: fewer than K hits, sentinel
                tails
      random    random rows, about half the pairs within cr
    """
    rng = np.random.default_rng(seed)
    q = rng.integers(-2, 3, (R, d)).astype(np.float32)
    p = rng.integers(-2, 3, (N, d)).astype(np.float32)
    gid = rng.permutation(N).astype(np.int32) * 3 + 1
    valid = np.ones(N, np.int32)
    cr2 = 1e6
    if case == "none":
        q += 0.5                       # dist^2 >= d / 4
        cr2 = 0.1
    elif case == "tightens":
        p[:, 0] = (N - np.arange(N)) // 2
    elif case == "ties":
        q[:] = 0.0
        p[:] = 1.0 + (rng.random(N) < 0.5)[:, None]
        gid = np.arange(N, dtype=np.int32)[::-1] * 3 + 1
    elif case == "few":
        valid[:] = 0
        for b0 in range(0, N, span):
            idx = np.arange(b0, min(b0 + span, N))
            valid[rng.choice(idx, min(K - 1, len(idx)), replace=False)] = 1
    else:
        cr2 = 30.0
    pb = (np.arange(N) // span).astype(np.int32)
    qb = (np.arange(R) % 4).astype(np.int32)
    return q, p, gid, valid, pb, qb, cr2


@pytest.mark.parametrize("K", [1, 10, 100, 128])
@pytest.mark.parametrize("case", ["none", "tightens", "ties", "few",
                                  "random"])
def test_insertion_merge_is_exact_on_both_kernels(case, K):
    """Both kernels' top-K merge, which runs one insertion pass per
    entering pair of a tile's busiest row, gives the oracle's answers
    bit for bit; both visit the same tiles in the same order, so they
    run the same passes, and a tile that improves no row runs none."""
    from repro.kernels.bucket_search import (bucket_gather_pallas,
                                             bucket_search_pallas)
    span = 200
    q, p, gid, valid, pb, qb, cr2 = _merge_case(case, K, span=span)
    R, N = q.shape[0], p.shape[0]
    zeros = lambda n: np.zeros((n, 1), np.int32)
    query = QueryBatch.build(jnp.asarray(q),
                             jnp.asarray(np.hstack([zeros(R), qb[:, None]])),
                             jnp.ones((R, 1), jnp.int32))
    store = StoreView.build(jnp.asarray(p),
                            jnp.asarray(np.hstack([zeros(N), pb[:, None]])),
                            jnp.asarray(gid), jnp.asarray(valid))
    want = [np.asarray(a) for a in ref.bucket_search_ref(
        query=query, store=store, cr2=cr2, L=1, K=K)]
    *got_s, passes_s = bucket_search_pallas(
        query=query, store=store, cr2=cr2, L=1, K=K, interpret=True)
    start = jnp.asarray(qb * span, jnp.int32)
    end = jnp.asarray(np.minimum((qb + 1) * span, N), jnp.int32)
    *got_g, passes_g = bucket_gather_pallas(
        jnp.zeros((R // 128,), jnp.int32), query.q, query.qsq, start, end,
        store.points, store.psq, store.gid, store.valid, cr2, K=K,
        G=N // 128, interpret=True)
    for got in (got_s, got_g):
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), b)
    steps = (R // 128) * (N // 128)
    assert int(passes_s) == int(passes_g) <= K * steps
    assert (int(passes_s) == 0) == (case == "none" or (case == "few"
                                                      and K == 1))
    if case == "few":
        assert (want[2] < K).all()
    if case in ("tightens", "ties") and K < 128:
        assert (want[2] > K).any()        # more hits than the top K holds


def test_bucket_search_no_rxn_buffer():
    """The streaming-reduction contract: per-grid-step VMEM residency is
    a function of (d, L, K) only, and the kernel's HBM outputs are
    O(R*K) -- no O(R*N) distance matrix anywhere."""
    from repro.kernels.bucket_search import vmem_bytes_per_step
    d, L, K = 64, 16, 32
    step = vmem_bytes_per_step(d, L, K)
    assert step < 4 * 2 ** 20  # well inside the ~16 MB VMEM budget
    # independent of problem size by construction (no R/N argument), and
    # the traced computation carries no (R, N)-shaped value anywhere --
    # walk every eqn output shape recursively through sub-jaxprs (pjit,
    # pallas_call kernel body, where the tiles are (TILE_R, TILE_N))
    # with the analyzer's structural iterator.
    from repro.analysis import jaxpr_pass

    def shapes(cj):
        for eqn in jaxpr_pass.iter_eqns(cj):
            for var in eqn.outvars:
                yield getattr(var.aval, "shape", ())

    R, N = 256, 1024
    query, store = _qs(_bucket_case(jax.random.PRNGKey(1), R, N, d, L))
    jaxpr = jax.make_jaxpr(
        lambda qb, sv: ops.bucket_search(query=qb, store=sv, cr2=2.5,
                                         L=L, k=K))(query, store)
    assert (R, N) not in set(shapes(jaxpr))
    # positive control: the same walk DOES see the dense (R, N) matrix in
    # the jnp oracle, so the assertion above has teeth
    jaxpr_ref = jax.make_jaxpr(
        lambda qb, sv: ref.bucket_search_ref(query=qb, store=sv, cr2=2.5,
                                             L=L, K=K))(query, store)
    assert (R, N) in set(shapes(jaxpr_ref))


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,Hkv,Sq,Sk,dh", [
    (1, 2, 2, 128, 128, 64),
    (2, 4, 2, 128, 256, 32),    # GQA group 2
    (1, 8, 1, 256, 256, 64),    # MQA
    (1, 2, 2, 100, 100, 64),    # unaligned -> padding path
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(B, H, Hkv, Sq, Sk, dh, causal):
    if causal and Sq != Sk:
        pytest.skip("causal requires aligned q/k here")
    key = jax.random.PRNGKey(B * Sq + Sk)
    kq, kk, kv = jax.random.split(key, 3)
    q = _rand(kq, (B, H, Sq, dh), scale=0.5)
    k = _rand(kk, (B, Hkv, Sk, dh), scale=0.5)
    v = _rand(kv, (B, Hkv, Sk, dh), scale=0.5)
    got = ops.flash_attention(q, k, v, causal=causal)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    key = jax.random.PRNGKey(3)
    kq, kk, kv = jax.random.split(key, 3)
    q = _rand(kq, (1, 2, 128, 64), jnp.bfloat16, 0.5)
    k = _rand(kk, (1, 2, 128, 64), jnp.bfloat16, 0.5)
    v = _rand(kv, (1, 2, 128, 64), jnp.bfloat16, 0.5)
    got = ops.flash_attention(q, k, v, causal=True)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=0.05, atol=0.05)


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,G,P,N", [
    (1, 128, 2, 2, 16, 16),
    (2, 256, 4, 1, 32, 16),   # grouped B/C broadcast
    (1, 100, 2, 2, 8, 8),     # unaligned seq -> padding path
])
def test_ssd_scan_matches_ref(B, S, H, G, P, N):
    key = jax.random.PRNGKey(S + P)
    ks = jax.random.split(key, 5)
    x = _rand(ks[0], (B, S, H, P), scale=0.5)
    a_log = jax.random.uniform(ks[1], (H,), minval=-2.0, maxval=0.5)
    b = _rand(ks[2], (B, S, G, N), scale=0.3)
    c = _rand(ks[3], (B, S, G, N), scale=0.3)
    dt = jax.nn.softplus(_rand(ks[4], (B, S, H)))
    got = ops.ssd_scan(x, a_log, b, c, dt)
    want = ref.ssd_scan_ref(x, a_log, b, c, dt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_ssd_scan_state_carry_across_chunks():
    """A single impulse at t=0 must echo with exp decay far beyond the
    chunk boundary -- proves the VMEM state actually carries."""
    B, S, H, P, N = 1, 256, 1, 4, 4
    x = jnp.zeros((B, S, H, P)).at[0, 0].set(1.0)
    a_log = jnp.asarray([-1.0])     # slow decay: a = -exp(-1) ~ -0.37
    b = jnp.ones((B, S, H, N)) * 0.5
    c = jnp.ones((B, S, H, N)) * 0.5
    dt = jnp.ones((B, S, H)) * 0.1
    got = np.asarray(ops.ssd_scan(x, a_log, b, c, dt))
    want = np.asarray(ref.ssd_scan_ref(x, a_log, b, c, dt))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    assert abs(got[0, 200, 0, 0]) > 0  # impulse visible past chunk 1
