"""What the query scan saw, and the served path's spans.

  * ops level: ``bucket_search`` reports, in ``SCAN_COUNTERS`` order,
    the overflow fallback, the tiles a row tile needed, the window G,
    the expanded probe rows with a non-empty span (equal to a numpy count
    of the distinct non-empty spans ``csr_probe_spans`` gives) and all
    expanded rows; zeros off the CSR path;
  * served: a gather window forced too small falls back on every scan
    step, a roomy one on none, with the same answers; the synchronous
    flush and the async pipeline count the same on the same calls; the
    fallback's grid follows the store's live extent, and the scan step
    compiles once per power-of-two bucket of the tail's tiles;
  * spans: under a CPU profiler trace the spans of one batch carry its
    number as the arg ``batch``.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.core import DistributedLSHIndex, LSHConfig, Scheme, store_layout
from repro.kernels import ops
from repro.kernels.types import QueryBatch, StoreView
from repro.serving import (AsyncLSHService, BatchRecord, ServiceStats,
                           ShardedLSHService)

COL = {name: i for i, name in enumerate(ops.SCAN_COUNTERS)}


def _rows(rng, shape, integer):
    """Normal rows, or small integers (every dist^2 exact in f32)."""
    x = rng.integers(-2, 3, shape) if integer else rng.normal(size=shape)
    return jnp.asarray(x.astype(np.float32))


def _sorted_store(n=300, d=8, n_buckets=40, seed=0, tail_only=False,
                  integer=False):
    """A bucket-sorted store; ``tail_only`` gives the same rows as an
    unsorted tail (``n_sorted == 0``), which the full scan reads."""
    rng = np.random.default_rng(seed)
    packed = np.zeros((n, 2), np.int32)
    packed[:, 1] = np.sort(rng.integers(0, n_buckets, n))
    table = np.zeros(n, np.int32)
    bs, be = store_layout.bucket_spans(table, packed)
    return StoreView.build(
        _rows(rng, (n, d), integer),
        jnp.asarray(packed), jnp.arange(n, dtype=jnp.int32),
        jnp.ones((n,), jnp.int32), bucket_start=jnp.asarray(bs),
        bucket_end=jnp.asarray(be), n_sorted=0 if tail_only else n)


def _probes(R=40, L=4, d=8, n_buckets=50, seed=1, integer=False):
    """Probes of buckets 0..n_buckets (some absent from the store), some
    repeated within a row, some switched off."""
    rng = np.random.default_rng(seed)
    qb = np.zeros((R, L, 2), np.int32)
    qb[..., 1] = rng.integers(0, n_buckets, (R, L))
    qb[:, 1, 1] = qb[:, 0, 1]                    # a repeat in every row
    probe = (rng.random((R, L)) < 0.8).astype(np.int32)
    return QueryBatch.build(
        _rows(rng, (R, d), integer),
        jnp.asarray(qb.reshape(R, 2 * L)), jnp.asarray(probe))


def test_counters_report_what_the_guard_saw():
    store, query = _sorted_store(), _probes()
    R, L = query.probe.shape
    start, end = (np.asarray(a) for a in ops.csr_probe_spans(query, store))
    spans = sorted({(r, s, e) for r in range(R)
                    for s, e in zip(start[r], end[r]) if e > s},
                   key=lambda t: t[1])
    # each run of 128 live spans, in start order, is one row tile
    tn, tr = ops.TILE_N, ops.TILE_R
    need = max((max((e - 1) // tn for _, _, e in spans[i:i + tr])
                - min(s // tn for _, s, _ in spans[i:i + tr]) + 1)
               for i in range(0, len(spans), tr))
    assert need > 1 and len(spans) > 0
    for window in (1, need):
        *_, c = ops.bucket_search(query=query, store=store, cr2=4.0, L=L,
                                  k=4, window_tiles=window)
        c = np.asarray(c)
        assert c.shape == (len(ops.SCAN_COUNTERS),)
        assert c[COL["probes_live"]] == len(spans)
        assert c[COL["probes_expanded"]] == -(-R * L // tr) * tr
        assert c[COL["window_tiles"]] == window
        assert c[COL["need_tiles"]] == need
        assert c[COL["fallback"]] == int(need > window)


@pytest.mark.parametrize("kw", [dict(tail_only=True),
                                dict(use_kernel=False)])
def test_counters_are_zero_off_the_csr_path(kw):
    """The guard's counters are zero there (the full-scan kernel over
    the rows as a tail, and the oracle); what the path returned is
    counted on every path."""
    store = _sorted_store(tail_only=kw.get("tail_only", False))
    query = _probes()
    _, g, cnt, c = ops.bucket_search(
        query=query, store=store, cr2=4.0, L=4, k=4,
        use_kernel=kw.get("use_kernel", True))
    c = np.asarray(c)
    assert not c[:len(ops.GUARD_COUNTERS)].any()
    assert c[COL["hits"]] == np.asarray(cnt).sum() > 0
    assert c[COL["filled"]] == (np.asarray(g) != ops.IMAX).sum() > 0
    # one row tile over the store's 300 rows, padded to 3 store tiles
    assert c[COL["grid_steps"]] == (3 if kw.get("use_kernel", True) else 0)


def _reckoned_passes(query, store, cr2, K):
    """The full scan's merge passes counted in NumPy: over its (row
    tile, store tile) grid steps, in order, the sum of min(K, the most
    pairs one row of the tile brings that are lex-smaller in (dist^2,
    gid) than its running K-th pair), each row's top K updated tile by
    tile."""
    q, p = (np.asarray(a, np.float64) for a in (query.q, store.points))
    d2 = ((q[:, None, :] - p[None, :, :]) ** 2).sum(-1)   # exact: integers
    qb, pb = np.asarray(query.buckets)[:, 1::2], np.asarray(store.buckets)
    probe, gid = np.asarray(query.probe) > 0, np.asarray(store.gid)
    hit = (((qb[:, :, None] == pb[None, None, :, 1]) & probe[:, :, None])
           .any(axis=1) & (np.asarray(store.valid) > 0)[None]
           & (d2 <= cr2))
    R, N = hit.shape
    total = 0
    for r0 in range(0, R, ops.TILE_R):
        rows = range(r0, min(r0 + ops.TILE_R, R))
        top = {r: [] for r in rows}
        for c0 in range(0, N, ops.TILE_N):
            most = 0
            for r in rows:
                pairs = [(d2[r, c], gid[c])
                         for c in range(c0, min(c0 + ops.TILE_N, N))
                         if hit[r, c]]
                kth = (top[r][K - 1] if len(top[r]) >= K
                       else (np.inf, ops.IMAX))
                most = max(most, sum(pr < kth for pr in pairs))
                top[r] = sorted(top[r] + pairs)[:K]
            total += min(K, most)
    return total


@pytest.mark.parametrize("K", [4, 100])
@pytest.mark.parametrize("path", ["unsorted", "fallback", "csr", "oracle"])
def test_merge_passes_count_the_insertions(path, K):
    """The full scan's passes (an unsorted store, or the fallback of a
    sorted one) are the NumPy reckoning's; on every path they are at
    most K a grid step (0 on the oracle), and 0 when nothing lies within
    cr."""
    store = _sorted_store(tail_only=path == "unsorted", integer=True)
    query = _probes(integer=True)
    kw = dict(query=query, store=store, L=4, k=K,
              use_kernel=path != "oracle",
              window_tiles=1 if path == "fallback" else 3)
    *_, c = ops.bucket_search(cr2=12.0, **kw)
    c = np.asarray(c)
    assert c[COL["fallback"]] == (path == "fallback")
    passes, steps = c[COL["merge_passes"]], c[COL["grid_steps"]]
    assert passes <= K * steps
    if path == "oracle":
        assert passes == steps == 0
    else:
        assert passes > 0
    if path in ("unsorted", "fallback"):
        assert passes == _reckoned_passes(query, store, 12.0, K)
    *_, c = ops.bucket_search(cr2=0.5, **kw)   # no integer row that close
    c = np.asarray(c)
    assert c[COL["hits"]] == c[COL["merge_passes"]] == 0


def test_records_without_the_column_read_no_merge_passes():
    """A record kept from a program that counted eight columns reads 0
    merge passes, as one of five reads 0 hits; the stats sum and print
    them."""
    def rec(counters):
        return BatchRecord(
            seq=0, reason="full", take=1, pad=0, drops=0, routed_rows=1,
            admission_wait_s=0.0, t_enqueue=0.0, t_enqueued=0.0,
            counters=np.array([counters], np.int32))
    old, new = rec([0, 0, 0, 0, 128, 5, 4, 3]), rec(
        [0, 0, 0, 0, 128, 5, 4, 3, 7])
    assert (old.grid_steps, old.merge_passes) == (3, 0)
    assert new.merge_passes == 7
    stats = ServiceStats()
    for r in (old, new):
        stats.record_batch(r)
    assert stats.scan_merge_passes == 7
    assert "grid_steps=6 merge_passes=7 " in stats.summary()


def _index():
    cfg = LSHConfig(d=8, k=4, W=1.2, r=0.3, c=2.0, L=4, n_shards=1,
                    scheme=Scheme.LAYERED, seed=0)
    idx = DistributedLSHIndex(cfg, make_mesh((1,), ("shard",)),
                              use_kernel=True, k_neighbors=4)
    rng = np.random.default_rng(0)
    data = rng.normal(size=(640, 8)).astype(np.float32)
    idx.build(jnp.asarray(data))
    idx.compact()
    queries = data[:24] + rng.normal(scale=0.05, size=(24, 8)).astype(
        np.float32)
    return idx, queries


@pytest.fixture(scope="module")
def served():
    idx, queries = _index()
    assert idx.layout["n_sorted"] > 128      # more than one store tile
    return idx, queries


def _serve(svc, queries):
    handles = svc.submit_batch(queries)
    svc.drain()
    return handles


@pytest.mark.parametrize("window", ["tight", "roomy"])
def test_window_decides_the_fallback(served, window, monkeypatch):
    idx, queries = served
    n_tiles = -(-idx.store.n_sorted // ops.TILE_N)
    monkeypatch.setattr(idx, "_gather_window",
                        lambda n: 1 if window == "tight" else n_tiles)
    svc = ShardedLSHService(idx, bucket_size=8, max_latency_ms=float("inf"))
    handles = _serve(svc, queries)
    st = svc.stats
    recs = {h.batch.seq: h.batch for h in handles}
    assert sorted(recs) == [0, 1, 2]
    assert st.scan_steps == 3 == st.batches
    assert [r.window_tiles for r in recs.values()] == [
        1 if window == "tight" else n_tiles] * 3
    if window == "tight":
        assert max(r.need_tiles for r in recs.values()) > 1
        assert st.scan_fallbacks == st.scan_steps
    else:
        assert st.scan_fallbacks == 0
    assert [r.fallback for r in recs.values()] == [window == "tight"] * 3
    assert all(0 < r.probes_live <= r.probes_expanded
               for r in recs.values())
    # the window moves work, never answers
    ref = idx.query(jnp.asarray(queries[:8]))
    np.testing.assert_array_equal(
        np.stack([h.gids for h in handles[:8]]), ref.topk_gid)


def test_sync_and_async_count_the_same(served):
    idx, queries = served
    sync = ShardedLSHService(idx, bucket_size=8,
                             max_latency_ms=float("inf"))
    sync_handles = _serve(sync, queries)
    with AsyncLSHService(idx, bucket_size=8,
                         max_latency_ms=float("inf")) as asvc:
        handles = _serve(asvc, queries)
        st = asvc.stats
        # counted before the handles resolved
        assert all(h.done for h in handles) and st.scan_steps == 3
    for name in ("scan_steps", "scan_fallbacks", "admitted", "queries",
                 "batches"):
        assert getattr(st, name) == getattr(sync.stats, name), name
    for a, s in zip(handles[::8], sync_handles[::8]):
        assert a.batch.seq == s.batch.seq
        np.testing.assert_array_equal(a.batch.counters, s.batch.counters)
    assert st.admission_wait_s >= 0.0 and st.admitted == 24
    assert "fallbacks=" in st.summary() and "qps=" not in st.summary()
    assert not hasattr(st, "query_time_s")


def test_grid_steps_follow_the_extent_and_one_bucket_compiles_once(
        monkeypatch):
    """A full scan's grid is its row tiles times the store tiles up to
    the live extent, not the capacity's; inserts that keep the tail in
    one power-of-two bucket of tiles add no scan compile, and one that
    crosses into the next adds one."""
    idx, queries = _index()              # its own: the inserts write
    monkeypatch.setattr(idx, "_gather_window", lambda n: 1)   # fall back
    tiles = lambda n: -(-n // ops.TILE_N)
    scan_keys = lambda: sum(key[0] == "scan" for key in idx._query_fns)
    more = np.random.default_rng(3).normal(size=(150, 8)).astype(
        np.float32)
    with AsyncLSHService(idx, bucket_size=8,
                         max_latency_ms=float("inf")) as svc:
        steps, keys = [], []
        for rows in (queries[:4], queries[4:20], more):
            svc.insert(rows).result(timeout=60)
            rec = _serve(svc, queries[:8])[0].batch
            assert rec.fallback
            steps.append(rec.grid_steps)
            keys.append(scan_keys())
    st = idx.store
    assert idx.layout["tail_hw"] == 170 and st.capacity > st.n_sorted + 256
    # one row tile (8 queries) over 1, 1 and 2 tiles of tail
    assert steps == [tiles(st.n_sorted + ops.TILE_N)] * 2 + [
        tiles(st.n_sorted + 2 * ops.TILE_N)]
    assert steps[-1] < tiles(st.capacity)
    assert keys == [keys[0], keys[0], keys[0] + 1]


def test_spans_of_one_batch_share_its_number(tmp_path):
    idx, queries = _index()              # its own: the insert writes
    with AsyncLSHService(idx, bucket_size=8,
                         max_latency_ms=float("inf")) as svc:
        _serve(svc, queries[:8])          # compiled outside the trace
        jax.profiler.start_trace(str(tmp_path))
        try:
            _serve(svc, queries[8:24])
            svc.insert(queries[:4]).result(timeout=60)
            svc.drain()
            n_live = idx.n_live
            idx.compact()       # the engine is idle: nothing is admitted
        finally:
            jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    spans = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("lsh."):
                    spans.setdefault(ev.name, []).append(
                        {k: v for k, v in ev.stats})
    batches = lambda name: sorted(a["batch"] for a in spans[name])
    assert batches("lsh.enqueue") == [1, 2]
    for name in ("lsh.dispatch", "lsh.scan", "lsh.return", "lsh.retire",
                 "lsh.fetch", "lsh.resolve"):
        assert batches(name) == [1, 2], name
    assert {a["reason"] for a in spans["lsh.enqueue"]} == {"full"}
    assert {a["take"] for a in spans["lsh.enqueue"]} == {8}
    write, = spans["lsh.write"]
    assert write["kind"] == "insert" and write["rows"] == 4
    assert len(spans["lsh.drain_barrier"]) == 1
    assert spans["lsh.merge"] == [{"n_live": n_live}]
