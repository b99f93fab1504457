"""CSR bucket-sorted store tests (the bucket-gather tentpole contract).

  * property: the sorted-CSR gather path answers BITWISE identically to
    the full-scan fallback a one-tile gather window forces -- distances
    compared as uint32 bit patterns --
    for T in {1, 2, 4} and every tail state the LSM layout can reach:
    freshly compacted (tail 0), a small unsorted tail, a tail past the
    merge threshold (auto-merge fires), and post-delete tombstones in
    the sorted region;
  * the same bitwise identity holds after an elastic restore onto a
    different shard count (subprocess, 8 host devices);
  * the kernels' full scans stop at the store's live extent (the tail's
    high-water mark): after every mutation sequence that moves the mark
    the bounded kernels answer bitwise as the kernels over every slot,
    and as the jnp oracle, which scans every slot (its distances to the
    kernels' tolerance);
  * kernel unit tests on a hand-built sorted store: empty bucket,
    single-row bucket, fully tombstoned bucket -- spans and results.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.core import DistributedLSHIndex, LSHConfig, Scheme, store_layout
from repro.data import planted_random
from repro.kernels import ops
from repro.kernels.types import QueryBatch, StoreView

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_MAX = np.float32(np.finfo(np.float32).max)
IMAX = np.iinfo(np.int32).max


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


def _assert_csr_equals_full(idx, queries):
    """Query once through the CSR gather, once under a one-tile gather
    window, which forces the full-scan fallback; the results must agree
    bit-for-bit."""
    a = idx.query(queries)
    idx._gather_window = lambda n: 1
    try:
        b = idx.query(queries)
    finally:
        del idx._gather_window
    assert b.scan_counters[:, 0].all(), b.scan_counters   # fell back
    np.testing.assert_array_equal(_bits(a.topk_dist), _bits(b.topk_dist))
    np.testing.assert_array_equal(a.topk_gid, b.topk_gid)
    np.testing.assert_array_equal(a.n_within_cr, b.n_within_cr)
    np.testing.assert_array_equal(a.fq, b.fq)
    return a


# ---------------------------------------------------------------------------
# Property: CSR == full scan through every LSM tail state (single shard)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T", [1, 2, 4])
def test_csr_bitwise_equals_full_scan_across_tail_states(T):
    cfg = LSHConfig(d=32, k=8, W=1.2, r=0.3, c=2.0, L=8, n_shards=1,
                    scheme=Scheme.LAYERED, seed=0, n_tables=T)
    mesh = make_mesh((1,), ("shard",))
    data, queries, _ = planted_random(n=512, m=48, d=32, r=0.3, seed=0)
    data, queries = jnp.asarray(data), jnp.asarray(queries)
    idx = DistributedLSHIndex(cfg, mesh, use_kernel=True, k_neighbors=4,
                              merge_min_rows=32, merge_frac=0.1)
    idx.build(data[:384])
    assert idx.layout["n_sorted"] == 0        # bulk build: legacy layout

    # tail = 0: freshly compacted, everything in the sorted region
    idx.compact()
    lay = idx.layout
    assert lay["n_sorted"] > 0 and lay["tail_rows"] == 0
    assert lay["sorted_rows"] == 384 * T == idx.n_live
    qr = _assert_csr_equals_full(idx, queries)

    # small tail: below both merge gates, rows stay unsorted
    idx.insert(data[384:388])
    lay = idx.layout
    assert lay["tail_rows"] == 4 * T and lay["merges"] == 1
    assert lay["sorted_rows"] + lay["tail_rows"] == idx.n_live
    _assert_csr_equals_full(idx, queries)

    # tombstones inside the sorted region: delete hits from the last run
    victims = np.unique(
        qr.topk_gid[:, 0][np.isfinite(qr.topk_dist[:, 0])])[:8]
    if len(victims):
        dr = idx.delete(victims)
        assert dr.n_deleted == T * len(victims)
        assert idx.layout["sorted_rows"] + idx.layout["tail_rows"] \
            == idx.n_live
        _assert_csr_equals_full(idx, queries)

    # tail past the merge threshold: the insert itself folds it back in
    idx.insert(data[388:512])
    lay = idx.layout
    assert lay["tail_rows"] == 0 and lay["merges"] >= 2
    assert lay["sorted_rows"] == idx.n_live
    _assert_csr_equals_full(idx, queries)


# ---------------------------------------------------------------------------
# Elastic restore keeps the sorted layout and the bitwise identity
# ---------------------------------------------------------------------------

@pytest.mark.multidevice
def test_csr_bitwise_after_elastic_restore():
    script = """
    import os, tempfile
    import jax, numpy as np
    import jax.numpy as jnp
    from repro.compat import make_mesh
    from repro.core import LSHConfig, Scheme, DistributedLSHIndex
    from repro.data import planted_random
    from repro import persist

    cfg = LSHConfig(d=32, k=8, W=1.2, r=0.3, c=2.0, L=8, n_shards=8,
                    scheme=Scheme.LAYERED, seed=0, n_tables=2)
    mesh8 = make_mesh((8,), ("shard",))
    mesh4 = make_mesh((4,), ("shard",), devices=jax.devices()[:4])
    data, queries, _ = planted_random(n=768, m=64, d=32, r=0.3, seed=0)
    data, queries = jnp.asarray(data), jnp.asarray(queries)

    idx = DistributedLSHIndex(cfg, mesh8, use_kernel=True, k_neighbors=4)
    idx.build(data)
    with tempfile.TemporaryDirectory() as tmp:
        persist.snapshot(idx, tmp)
        r = persist.restore(tmp, mesh4, n_shards=4, use_kernel=True)
    lay = r.layout
    assert lay["n_sorted"] > 0 and lay["tail_rows"] == 0, lay
    assert lay["sorted_rows"] == r.n_live == 768 * 2, lay

    a = r.query(queries)
    r._gather_window = lambda n: 1      # forces the full-scan fallback
    b = r.query(queries)
    assert b.scan_counters[:, 0].all(), b.scan_counters
    np.testing.assert_array_equal(
        np.asarray(a.topk_dist).view(np.uint32),
        np.asarray(b.topk_dist).view(np.uint32))
    np.testing.assert_array_equal(a.topk_gid, b.topk_gid)
    np.testing.assert_array_equal(a.n_within_cr, b.n_within_cr)
    # and both agree with the pre-restore 8-shard answer on gids
    qr = idx.query(queries)
    np.testing.assert_array_equal(a.topk_gid, qr.topk_gid)
    print("OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "OK" in out.stdout


# ---------------------------------------------------------------------------
# The full scans stop at the live extent; answers do not move
# ---------------------------------------------------------------------------

EXTENT_CASES = ["tail", "reuse_tombstones", "grow_past_pow2", "compact",
                "legacy", "two_shards"]
GRID_STEPS = ops.SCAN_COUNTERS.index("grid_steps")


def _extent_answers(idx, queries, ext):
    """The query's answers and scan counters with the full scans bounded
    at ``ext`` (the kernels) or unbounded (``ext=None``: the oracle)."""
    idx._query_fns.clear()              # keyed on neither knob below
    idx.use_kernel = ext is not None
    idx._scan_extent = lambda: ext
    try:
        r = idx.query(queries)
    finally:
        del idx._scan_extent
        idx.use_kernel = True
    return r


def _check_extent(idx, queries):
    """The bounded kernels against the kernels over every slot and the
    oracle, through the CSR gather (when the store has a sorted region)
    and the full-scan fallback a one-tile window forces."""
    st = idx.store
    ext, cap = idx._scan_extent(), st.capacity
    hw = idx.layout["tail_hw"]
    assert st.n_sorted + hw <= ext < cap, (st.n_sorted, hw, ext, cap)
    # no valid row past the mark, and the mark is the highest valid row
    valid = np.asarray(st.valid)
    assert not valid[:, st.n_sorted + hw:].any()
    assert hw == 0 or valid[:, st.n_sorted + hw - 1].any()
    oracle = _extent_answers(idx, queries, None)
    assert oracle.n_within_cr.sum() > 0
    windows = ["default"]
    if st.n_sorted:
        windows.append("tight")
    for window in windows:
        if window == "tight":
            idx._gather_window = lambda n: 1
        try:
            got = _extent_answers(idx, queries, ext)
            full = _extent_answers(idx, queries, cap)
        finally:
            idx.__dict__.pop("_gather_window", None)
        np.testing.assert_array_equal(_bits(got.topk_dist),
                                      _bits(full.topk_dist))
        # the oracle's distances round as the kernels' do not
        np.testing.assert_allclose(got.topk_dist, oracle.topk_dist,
                                   rtol=1e-5, atol=1e-5)
        for want in (full, oracle):
            np.testing.assert_array_equal(got.topk_gid, want.topk_gid)
            np.testing.assert_array_equal(got.n_within_cr, want.n_within_cr)
        fell_back = got.scan_counters[:, 0].any()
        if window == "tight" or not st.n_sorted:
            # a full scan of the store: row tiles x extent tiles
            assert window == "default" or fell_back
            tiles = lambda n: -(-n // ops.TILE_N)
            rows = got.scan_counters[:, GRID_STEPS] // tiles(ext)
            assert (rows >= 1).all()
            np.testing.assert_array_equal(
                got.scan_counters[:, GRID_STEPS], rows * tiles(ext))
            np.testing.assert_array_equal(
                full.scan_counters[:, GRID_STEPS], rows * tiles(cap))


def _extent_case(case):
    """Run one mutation sequence and check the bounded scans after it."""
    S = 2 if case == "two_shards" else 1
    cfg = LSHConfig(d=16, k=4, W=1.2, r=0.3, c=4.0, L=4, n_shards=S,
                    scheme=Scheme.LAYERED, seed=0)
    mesh = make_mesh((S,), ("shard",))
    data, _, _ = planted_random(n=1536, m=1, d=16, r=0.3, seed=1)
    rng = np.random.default_rng(2)
    near = np.concatenate([rng.choice(640, 16), 640 + rng.choice(896, 16)])
    queries = jnp.asarray(data[near] + rng.normal(
        scale=0.3 / 4, size=(32, 16)).astype(np.float32))
    data = jnp.asarray(data)
    idx = DistributedLSHIndex(cfg, mesh, use_kernel=True, k_neighbors=4,
                              merge_min_rows=10 ** 9)
    if case == "legacy":
        idx.build(data[:900])
        assert idx.store.n_sorted == 0 and idx.layout["tail_hw"] >= 900 // S
        return _check_extent(idx, queries)
    idx.build(data[:640])
    idx.compact()
    if case == "grow_past_pow2":
        idx.insert(data[640:700])          # one tile of tail
        assert idx._scan_extent() == idx.store.n_sorted + ops.TILE_N
        cap = idx.store.capacity
        idx.insert(data[700:1536])         # past 4 tiles, and the capacity
        assert idx.store.capacity > cap
        assert idx._scan_extent() == idx.store.n_sorted + 8 * ops.TILE_N
        return _check_extent(idx, queries)
    idx.insert(data[640:900])
    hw = idx.layout["tail_hw"]
    assert hw == 260 // S if S == 1 else hw > 0
    if case == "reuse_tombstones":
        idx.delete(np.arange(700, 760))
        assert idx.layout["tail_hw"] == hw      # deletes keep the mark
        idx.insert(data[900:950])               # into the freed slots
        assert idx.layout["tail_hw"] == hw
    elif case == "compact":
        idx.compact()
        assert idx.layout["tail_hw"] == 0
        assert idx._scan_extent() == idx.store.n_sorted
    elif case == "two_shards":
        # the tails differ: the SPMD scan takes the fuller shard's mark
        valid = np.asarray(idx.store.valid)[:, idx.store.n_sorted:]
        marks = [int(np.flatnonzero(v).max()) + 1 for v in valid]
        assert marks[0] != marks[1] and hw == max(marks), (marks, hw)
    return _check_extent(idx, queries)


@pytest.mark.parametrize("case", EXTENT_CASES)
def test_bounded_scans_answer_as_the_unbounded(case):
    if case != "two_shards":
        return _extent_case(case)
    script = f"""
    import sys
    sys.path.insert(0, {os.path.join(_REPO, "tests")!r})
    from test_csr_store import _extent_case
    _extent_case("two_shards")
    print("OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "OK" in out.stdout


# ---------------------------------------------------------------------------
# Kernel unit tests: hand-built sorted store, degenerate buckets
# ---------------------------------------------------------------------------

def _degenerate_store():
    """Six rows, buckets 0/2/3 present: bucket 0 holds three live rows,
    bucket 1 is ABSENT (empty probe target), bucket 2 holds one row,
    bucket 3 holds two rows that are both tombstoned."""
    d = 8
    packed = np.zeros((6, 2), np.int32)
    packed[:, 1] = [0, 0, 0, 2, 3, 3]
    table = np.zeros(6, np.int32)
    points = np.zeros((6, d), np.float32)
    points[:, 0] = np.arange(1, 7, dtype=np.float32)   # distinct dists
    valid = np.array([1, 1, 1, 1, 0, 0], np.int32)
    gid = np.arange(10, 16, dtype=np.int32)
    bs, be = store_layout.bucket_spans(table, packed)
    store = StoreView.build(
        jnp.asarray(points), jnp.asarray(packed), jnp.asarray(gid),
        jnp.asarray(valid), bucket_start=jnp.asarray(bs),
        bucket_end=jnp.asarray(be), n_sorted=6)
    # one query per target bucket 0..3, probing from the origin
    qb = np.zeros((4, 2), np.int32)
    qb[:, 1] = np.arange(4)
    query = QueryBatch.build(jnp.zeros((4, d), jnp.float32),
                             jnp.asarray(qb),
                             jnp.ones((4, 1), jnp.int32))
    return query, store


def _tail_only(kw):
    """The same search over the store's rows seen as an unsorted tail
    (``n_sorted == 0``): the full-scan kernel."""
    return dict(kw, store=dataclasses.replace(kw["store"], n_sorted=0))


def test_probe_spans_degenerate_buckets():
    query, store = _degenerate_store()
    start, end = ops.csr_probe_spans(query, store)
    np.testing.assert_array_equal(np.asarray(start)[:, 0], [0, 3, 3, 4])
    np.testing.assert_array_equal(np.asarray(end)[:, 0], [3, 3, 4, 6])


def test_gather_degenerate_buckets_match_full_scan():
    query, store = _degenerate_store()
    kw = dict(query=query, store=store, cr2=100.0, L=1, k=4)
    td, tg, cnt, _ = ops.bucket_search(**kw)
    td_f, tg_f, cnt_f, _ = ops.bucket_search(**_tail_only(kw))
    np.testing.assert_array_equal(_bits(td), _bits(td_f))
    np.testing.assert_array_equal(np.asarray(tg), np.asarray(tg_f))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_f))

    td, tg, cnt = np.asarray(td), np.asarray(tg), np.asarray(cnt)
    # bucket 0: its three rows by ascending distance, then sentinel
    np.testing.assert_array_equal(tg[0], [10, 11, 12, IMAX])
    assert np.all(np.diff(td[0, :3]) > 0) and td[0, 3] == F32_MAX
    # bucket 1 (absent) and bucket 3 (all tombstoned): no hits at all
    for r in (1, 3):
        assert np.all(tg[r] == IMAX) and np.all(td[r] == F32_MAX)
        assert cnt[r] == 0
    # bucket 2: exactly the single row
    np.testing.assert_array_equal(tg[2], [13, IMAX, IMAX, IMAX])
    assert cnt[2] == 1 and cnt[0] == 3


def test_gather_tight_radius_filters_inside_bucket():
    """cr2 between row distances: the span is scanned but only rows
    within cr count -- identical to the full scan's filter."""
    query, store = _degenerate_store()
    kw = dict(query=query, store=store, cr2=5.0, L=1, k=4)  # rows 1,2 only
    td, tg, cnt, _ = ops.bucket_search(**kw)
    td_f, tg_f, cnt_f, _ = ops.bucket_search(**_tail_only(kw))
    np.testing.assert_array_equal(_bits(td), _bits(td_f))
    np.testing.assert_array_equal(np.asarray(tg), np.asarray(tg_f))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_f))
    np.testing.assert_array_equal(np.asarray(tg)[0], [10, 11, IMAX, IMAX])
    assert np.asarray(cnt)[0] == 2
