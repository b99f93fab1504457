"""Top-100 answers through the served path, against the plain reference.

``AsyncLSHService`` on a one-device CPU mesh with the Pallas kernels (in
interpret mode) answers K = 100 with cr above every distance in the
data, so each answer is the exact top-100 of the query's LSH candidates
over the live set.  Three cases: the CSR gather over the sorted region
plus a full scan of the insert tail, the full-scan fallback forced by a
one-tile gather window, and the gather after deletes in both regions.
Each batch's ``hits``, ``filled`` and ``grid_steps`` are held to what
the test counts itself: every candidate of the reference, the ranks
min(K, candidates) and the kernels' grid from the store's shapes; its
``merge_passes`` to at most K a grid step.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.core import (DistributedLSHIndex, LSHConfig, Scheme,
                        lsh_topk_reference)
from repro.kernels import ops
from repro.serving import AsyncLSHService

K = 100
D = 16
BUILT, TAIL = 1536, 384
BUCKET = 64
FAR = 1e3        # where a deleted row goes for the reference: beyond cr
# k = 4, W = 2: a query's candidates number from a few to hundreds, so
# some answers fill all 100 ranks and some do not; cr = 6 is above every
# distance of N(0, 1/d) data in 16 dimensions (at most ~2.4 here)
CFG = LSHConfig(d=D, k=4, W=2.0, r=0.3, c=20.0, L=4, n_shards=1,
                scheme=Scheme.LAYERED, seed=0)


@pytest.fixture(scope="module")
def rows():
    rng = np.random.default_rng(7)
    x = (rng.standard_normal((BUILT + TAIL, D)) / np.sqrt(D)).astype(
        np.float32)
    near = rng.integers(0, BUILT + TAIL, BUCKET)
    q = x[near] + (rng.standard_normal((len(near), D)) * CFG.r
                   / np.sqrt(D)).astype(np.float32)
    return x, q, near


def _index(x, deleted=()):
    """Built and compacted (the sorted region), then a tail inserted."""
    idx = DistributedLSHIndex(CFG, make_mesh((1,), ("shard",)),
                              use_kernel=True, k_neighbors=K)
    idx.build(jnp.asarray(x[:BUILT]))
    idx.compact()
    idx.insert(jnp.asarray(x[BUILT:]))
    if len(deleted):
        idx.delete(np.asarray(deleted))
    st = idx.store
    assert 0 < st.n_sorted < st.capacity and idx.layout["tail_rows"] > 0
    return idx


@pytest.fixture(scope="module")
def index(rows):
    return _index(rows[0])


def _grid_steps(idx, rec):
    """The kernels' (row tile, store tile) grid of the branch taken: the
    scan searches one row tile (the bucket's 64 live rows); the gather
    expands them by L into row tiles of G store tiles each, beside the
    tail's full scan.  Both full scans stop at the store's live extent:
    the sorted region plus the tail's high-water mark rounded up to a
    power of two of tiles, short of the capacity here."""
    tiles = lambda n: -(-n // ops.TILE_N)
    st = idx.store
    ext = st.n_sorted + ops.TILE_N * 2 ** (
        tiles(idx.layout["tail_hw"]) - 1).bit_length()
    assert ext == idx._scan_extent() < st.capacity
    if rec.fallback:
        return tiles(ext)
    expanded = -(-BUCKET * CFG.L // ops.TILE_R)
    return expanded * rec.window_tiles + tiles(ext - st.n_sorted)


def _serve_and_check(idx, x, q, deleted=()):
    live = np.array(x, copy=True)
    live[np.asarray(deleted, np.int64)] = FAR
    with AsyncLSHService(idx, bucket_size=BUCKET,
                         max_latency_ms=float("inf")) as svc:
        handles = svc.submit_batch(q)
        svc.drain()
        stats = svc.stats
    # every candidate, in (distance, gid) order; slot i has qid i
    rd, rg = lsh_topk_reference(CFG, jnp.asarray(live), jnp.asarray(q),
                                len(x))
    n_cand = (rg != ops.IMAX).sum(axis=1)
    got_g = np.stack([h.gids for h in handles])
    got_d = np.stack([h.dists for h in handles])
    assert got_g.shape == (BUCKET, K)
    np.testing.assert_array_equal(got_g, rg[:, :K])
    fin = np.isfinite(rd[:, :K])
    np.testing.assert_array_equal(np.isfinite(got_d), fin)
    np.testing.assert_allclose(got_d[fin], rd[:, :K][fin], rtol=1e-4,
                               atol=1e-5)
    assert not np.isin(got_g, np.asarray(deleted, np.int64)).any()
    # answers that fill all 100 ranks and answers that do not
    assert (n_cand > K).any() and (n_cand < K).any(), n_cand
    rec = handles[0].batch
    assert all(h.batch is rec for h in handles)
    assert rec.hits == n_cand.sum() == sum(h.n_within_cr for h in handles)
    assert rec.filled == np.minimum(n_cand, K).sum()
    assert rec.grid_steps == _grid_steps(idx, rec)
    assert 0 < rec.merge_passes <= K * rec.grid_steps
    assert (stats.scan_hits, stats.scan_filled, stats.scan_grid_steps,
            stats.scan_merge_passes) == (
        rec.hits, rec.filled, rec.grid_steps, rec.merge_passes)
    assert (f"hits={rec.hits} filled={rec.filled} "
            f"grid_steps={rec.grid_steps} "
            f"merge_passes={rec.merge_passes}") in stats.summary()
    return rec


@pytest.mark.parametrize("window", ["roomy", "tight"])
def test_top100_gather_and_fallback(index, rows, window, monkeypatch):
    """The CSR gather with a tail (a window of every sorted tile) and
    the full-scan fallback (a one-tile window) give the reference's
    top-100."""
    x, q, _ = rows
    n_tiles = -(-index.store.n_sorted // ops.TILE_N)
    monkeypatch.setattr(index, "_gather_window",
                        lambda n: n_tiles if window == "roomy" else 1)
    rec = _serve_and_check(index, x, q)
    assert rec.fallback == (window == "tight")


def test_top100_after_deletes(rows, monkeypatch):
    """Deletes in the sorted region and the tail, a quarter of them rows
    the queries were planted near: no deleted gid is served and the rest
    of each answer moves up, as the reference over the live set has it."""
    x, q, near = rows
    rng = np.random.default_rng(8)
    deleted = np.unique(np.concatenate([
        near[::4], rng.choice(BUILT, 64, replace=False),
        BUILT + rng.choice(TAIL, 16, replace=False)]))
    idx = _index(x, deleted)
    monkeypatch.setattr(idx, "_gather_window",
                        lambda n: -(-idx.store.n_sorted // ops.TILE_N))
    assert not _serve_and_check(idx, x, q, deleted).fallback
