"""Async pipelined serving tests.

The acceptance contract for the double-buffered query pipeline and the
``AsyncLSHService`` worker front-end:

  * ``query()`` is its three compiled stages (dispatch / scan / return):
    calling them by hand answers BITWISE as ``query()``, which agrees
    with the jnp oracle, for T in {1, 2}, before and after inserts;
  * both front ends and a direct ``query()`` share one compiled program
    per stage;
  * driving ``AsyncLSHService`` with an interleaved insert/delete/query
    stream answers bitwise identically to ``ShardedLSHService`` on the
    same stream (the pipeline overlaps device work, never reorders);
  * crash with query batches in flight: the WAL holds every applied
    write (append-before-apply), so recovery converges bitwise to the
    synchronous reference over the durable prefix;
  * deadline flushes honor the injected clock;
  * admission backpressure: "reject" raises ``AdmissionFull`` and
    counts it, "block" parks the producer until the engine drains;
  * at most one background snapshot is in flight; extra requests are
    skipped and counted.

Multidevice contracts run in subprocesses (8 host devices); in-process
single-shard tests keep fast-lane coverage over the new modules.
"""
import importlib
import os
import subprocess
import sys
import textwrap
import threading
import time
from collections import Counter

import numpy as np
import pytest

pytestmark = pytest.mark.multidevice

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


COMMON = """
import os, tempfile
import jax, numpy as np
import jax.numpy as jnp
from repro.compat import make_mesh
from repro.core import LSHConfig, Scheme, DistributedLSHIndex
from repro.data import planted_random
from repro.serving import AsyncLSHService, ShardedLSHService
from repro import persist

D = 32
def make_cfg(S=8, T=1):
    return LSHConfig(d=D, k=8, W=1.2, r=0.3, c=2.0, L=8, n_shards=S,
                     scheme=Scheme.LAYERED, seed=0, n_tables=T)

mesh8 = make_mesh((8,), ("shard",))
data, queries, _ = planted_random(n=768, m=64, d=D, r=0.3, seed=0)
data, queries = jnp.asarray(data), jnp.asarray(queries)

def assert_same_result(a, b):
    np.testing.assert_array_equal(a.topk_gid, b.topk_gid)
    np.testing.assert_array_equal(a.topk_dist, b.topk_dist)
    np.testing.assert_array_equal(a.n_within_cr, b.n_within_cr)
    np.testing.assert_array_equal(a.fq, b.fq)
    np.testing.assert_array_equal(a.query_load, b.query_load)
    assert a.drops == b.drops
"""


def test_staged_query_bitwise_equals_fused():
    """query_dispatch/scan/return called by hand compose to EXACTLY
    query() -- including with the donated staging buffer and after a
    streaming insert -- and query() answers as the jnp oracle (its
    distances to the kernels' rounding)."""
    out = _run(COMMON + """
from repro.core.index import _host_query_result

def staged(idx, q, donate=False, k_neighbors=None):
    disp = idx.query_dispatch(q, donate=donate)
    scanned = idx.query_scan(disp, k_neighbors=k_neighbors)
    return _host_query_result(*idx.query_return(scanned), disp.fq,
                              scanned.recv_load, disp.drops,
                              scanned.counters)

def assert_oracle(got, want):
    np.testing.assert_array_equal(got.topk_gid, want.topk_gid)
    np.testing.assert_array_equal(got.n_within_cr, want.n_within_cr)
    np.testing.assert_array_equal(got.fq, want.fq)
    np.testing.assert_allclose(got.topk_dist, want.topk_dist,
                               rtol=1e-5, atol=1e-5)

for T in (1, 2):
    idx, oracle = (DistributedLSHIndex(make_cfg(T=T), mesh8,
                                       use_kernel=kernel, k_neighbors=5)
                   for kernel in (True, False))
    for ix in (idx, oracle):
        ix.build(data[:512], capacity=ix._store_capacity(4 * 768 * T))
    res = idx.query(queries)
    assert_same_result(staged(idx, queries), res)
    assert_oracle(res, oracle.query(queries))

    # donated staging buffer (the pipeline's mode): still bitwise
    buf = jnp.array(queries)
    assert_same_result(staged(idx, buf, donate=True), res)

    # a write between queries recompiles against the new store
    for ix in (idx, oracle):
        ix.insert(data[512:640])
    res = idx.query(queries, k_neighbors=3)
    assert_same_result(staged(idx, queries, k_neighbors=3), res)
    assert_oracle(res, oracle.query(queries, k_neighbors=3))
    print(f"staged OK T={T}")
print("OK")
""")
    assert "OK" in out


def test_async_stream_bitwise_equals_sync():
    """The tentpole equivalence: an interleaved insert/delete/query
    stream through AsyncLSHService answers bitwise identically to
    ShardedLSHService, for T in {1, 2}, with pipelining engaged
    (pipeline_depth=2, multiple buckets in flight)."""
    out = _run(COMMON + """
def drive(svc, is_async):
    '''Same admitted stream for both services; returns per-query rows.'''
    rng = np.random.default_rng(7)
    handles = []
    svc.insert(np.asarray(data[:256]))
    for step in range(4):
        qs = np.asarray(queries)[rng.permutation(64)[:48]]
        handles += svc.submit_batch(qs)           # 48 = 1.5 buckets
        lo = 256 + step * 64
        svc.insert(np.asarray(data[lo:lo + 64]))
        svc.delete(np.arange(step, 256 + step * 64, 17))
        handles += svc.submit_batch(np.asarray(queries)[:32])
    svc.drain()
    assert all(h.done for h in handles)
    return (np.stack([h.gids for h in handles]),
            np.stack([h.dists for h in handles]),
            np.asarray([h.fq for h in handles]))

for T in (1, 2):
    def build():
        idx = DistributedLSHIndex(make_cfg(T=T), mesh8, use_kernel=True,
                                  k_neighbors=5)
        idx.init_store(idx._store_capacity(4 * 768 * T))
        return idx
    sync = ShardedLSHService(build(), bucket_size=32,
                             max_latency_ms=float("inf"), k_neighbors=5)
    g0, d0, f0 = drive(sync, False)
    with AsyncLSHService(build(), bucket_size=32,
                         max_latency_ms=float("inf"), k_neighbors=5,
                         pipeline_depth=2) as asvc:
        g1, d1, f1 = drive(asvc, True)
        assert asvc.stats.inflight_peak >= 2, asvc.stats.inflight_peak
    np.testing.assert_array_equal(g0, g1)
    np.testing.assert_array_equal(d0, d1)
    np.testing.assert_array_equal(f0, f1)
    assert sync.stats.queries == asvc.stats.queries == 320
    print(f"async==sync OK T={T} inflight_peak={asvc.stats.inflight_peak}")
print("OK")
""")
    assert "OK" in out


def test_crash_with_batch_in_flight_recovers():
    """Kill the process (simulated: abandon the service without close)
    while query batches are in flight and writes are mid-stream; WAL
    replay converges bitwise to the synchronous reference holding every
    write whose append returned."""
    out = _run(COMMON + """
CAP = 4 * 768 * 2
with tempfile.TemporaryDirectory() as tmp:
    idx = DistributedLSHIndex(make_cfg(T=2), mesh8, k_neighbors=5)
    idx.init_store(CAP)
    wal = persist.WriteAheadLog(persist.wal_path(tmp),
                                group_commit_n=4)
    svc = AsyncLSHService(idx, bucket_size=32,
                          max_latency_ms=float("inf"), k_neighbors=5,
                          wal=wal)
    persist.snapshot(idx, tmp, wal=wal)           # boot snapshot
    svc.insert(np.asarray(data[:256])).result()
    h = svc.submit_batch(np.asarray(queries)[:48])   # 1 bucket in flight
    svc.insert(np.asarray(data[256:384])).result()   # applied, WAL'd
    svc.delete(np.arange(0, 256, 13)).result()
    h2 = svc.submit_batch(np.asarray(queries)[:16])  # parked partial
    # CRASH: no drain, no close -- in-flight batch + partial bucket die
    # with the process; the WAL survives (group window still open)
    wal.close()

    rr = persist.recover(tmp, mesh8, capacity=CAP, k_neighbors=5)
    assert rr.replayed_inserts == 2 and rr.replayed_deletes == 1

    ref = DistributedLSHIndex(make_cfg(T=2), mesh8, k_neighbors=5)
    ref.init_store(CAP)
    ref.insert(data[:256], gids=np.arange(256))
    ref.insert(data[256:384], gids=np.arange(256, 384))
    ref.delete(np.arange(0, 256, 13))
    assert_same_result(rr.index.query(queries), ref.query(queries))
    assert rr.index._next_gid == ref._next_gid == 384
print("OK")
""")
    assert "OK" in out


# ---------------------------------------------------------------------
# In-process single-shard tests (fast-lane coverage over the new code)
# ---------------------------------------------------------------------

def _small_index(T: int = 1, k_neighbors: int = 4, use_kernel=None):
    from repro.compat import make_mesh
    from repro.core import DistributedLSHIndex, LSHConfig, Scheme

    cfg = LSHConfig(d=8, k=4, W=1.2, r=0.3, c=2.0, L=4, n_shards=1,
                    scheme=Scheme.LAYERED, seed=0, n_tables=T)
    mesh = make_mesh((1,), ("shard",))
    idx = DistributedLSHIndex(cfg, mesh, k_neighbors=k_neighbors,
                              use_kernel=use_kernel)
    idx.init_store(idx._store_capacity(8 * 256 * T))
    return idx


def _small_data(n=96, m=24, seed=0):
    rng = np.random.default_rng(seed)
    data = rng.normal(size=(n, 8)).astype(np.float32)
    queries = data[:m] + rng.normal(scale=0.05, size=(m, 8)).astype(
        np.float32)
    return data, queries


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_async_stream_bitwise_inprocess():
    from repro.serving import AsyncLSHService, ShardedLSHService

    data, queries = _small_data()

    def drive(svc):
        handles = []
        svc.insert(data[:48])
        handles += svc.submit_batch(queries[:12])
        svc.delete(np.arange(0, 48, 5))
        svc.insert(data[48:])
        handles += svc.submit_batch(queries[12:])
        svc.drain()
        return (np.stack([h.gids for h in handles]),
                np.stack([h.dists for h in handles]))

    sync = ShardedLSHService(_small_index(), bucket_size=8,
                             max_latency_ms=float("inf"), k_neighbors=4)
    g0, d0 = drive(sync)
    with AsyncLSHService(_small_index(), bucket_size=8,
                         max_latency_ms=float("inf"),
                         k_neighbors=4) as asvc:
        g1, d1 = drive(asvc)
        st = asvc.stats
        assert st.queries == 24 and st.inserts == 96
        assert st.latency_p50_ms >= 0.0 and st.latency_p99_ms >= 0.0
        assert "lat(p50/p99)" in st.summary()
    np.testing.assert_array_equal(g0, g1)
    np.testing.assert_array_equal(d0, d1)


def test_front_ends_share_one_set_of_query_programs():
    """ShardedLSHService, AsyncLSHService and a direct query() with the
    same bucket size, K and donation run the same three compiled stage
    programs over a sorted store, and answer bitwise alike."""
    import jax.numpy as jnp

    from repro.serving import AsyncLSHService, ShardedLSHService

    data, queries = _small_data(n=640, m=8)
    idx = _small_index(use_kernel=True)
    idx.insert(jnp.asarray(data))
    idx.compact()
    assert idx.layout["n_sorted"] > 0

    sync = ShardedLSHService(idx, bucket_size=8,
                             max_latency_ms=float("inf"))
    served = [sync.submit_batch(queries)]
    sync.drain()
    with AsyncLSHService(idx, bucket_size=8,
                         max_latency_ms=float("inf")) as asvc:
        served.append(asvc.submit_batch(queries))
        asvc.drain()
    direct = idx.query(jnp.array(queries), donate=True)

    assert Counter(key[0] for key in idx._query_fns) == {
        "dispatch": 1, "scan": 1, "return": 1}, list(idx._query_fns)
    assert direct.n_within_cr.sum() > 0
    for handles in served:
        np.testing.assert_array_equal(
            np.stack([h.gids for h in handles]), direct.topk_gid)
        np.testing.assert_array_equal(
            np.stack([h.dists for h in handles]).view(np.uint32),
            direct.topk_dist.view(np.uint32))
        np.testing.assert_array_equal(
            [h.n_within_cr for h in handles], direct.n_within_cr)


def test_deadline_flush_uses_injected_clock():
    """A partial bucket flushes when the INJECTED clock passes the
    deadline -- wall time never does (SLO accounting is testable)."""
    from repro.serving import AsyncLSHService

    data, queries = _small_data()
    clock = FakeClock()
    with AsyncLSHService(_small_index(), bucket_size=8,
                         max_latency_ms=25.0, k_neighbors=4,
                         clock=clock) as svc:
        svc.insert(data[:48]).result(timeout=30)
        h = svc.submit(queries[0])
        time.sleep(0.2)           # real time passes; injected does not
        assert not h.done and svc.stats.flush_deadline == 0
        clock.t += 0.1            # 100ms > the 25ms SLO
        deadline = time.monotonic() + 30
        while not h.done and time.monotonic() < deadline:
            time.sleep(0.005)
        assert h.done and h.gids is not None
        assert svc.stats.flush_deadline == 1
        assert svc.stats.flush_manual == 0


def test_reject_admission_backpressure():
    from repro.serving import AdmissionFull, AsyncLSHService

    data, queries = _small_data()
    svc = AsyncLSHService(_small_index(), bucket_size=8,
                          max_latency_ms=float("inf"), k_neighbors=4,
                          queue_depth=2, admission="reject",
                          autostart=False)
    with pytest.raises(RuntimeError, match="engine not running"):
        svc.drain()
    svc.submit_batch(queries[:2])
    svc.insert(data[:8])
    with pytest.raises(AdmissionFull):
        svc.submit_batch(queries[2:4])
    assert svc.stats.rejects == 1
    assert svc.stats.queue_peak == 2
    svc.start()
    svc.drain()
    svc.close()
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit(queries[0])


def test_block_admission_backpressure():
    """admission='block' parks the producer on a full queue until the
    engine drains it -- no rejects, no loss."""
    from repro.serving import AsyncLSHService

    _, queries = _small_data()
    svc = AsyncLSHService(_small_index(), bucket_size=4,
                          max_latency_ms=float("inf"), k_neighbors=4,
                          queue_depth=1, admission="block",
                          autostart=False)
    svc.submit_batch(queries[:4])           # fills the queue
    handles = []
    blocked = threading.Thread(
        target=lambda: handles.extend(svc.submit_batch(queries[4:8])))
    blocked.start()
    blocked.join(timeout=0.3)
    assert blocked.is_alive()               # parked on the full queue
    svc.start()                             # engine drains -> unblocks
    blocked.join(timeout=30)
    assert not blocked.is_alive()
    svc.drain()
    assert all(h.done for h in handles)
    assert svc.stats.rejects == 0 and svc.stats.queries == 8
    svc.close()


def test_background_snapshot_at_most_one_in_flight(tmp_path):
    """While one background snapshot writes, further requests are
    skipped (counted), and join() surfaces the written file."""
    from repro import persist
    from repro.serving import AsyncLSHService

    # the package rebinds the name `snapshot` to the function, so the
    # submodule must be resolved through importlib for monkeypatching
    snapmod = importlib.import_module("repro.persist.snapshot")
    gate = threading.Event()
    real_write = snapmod._write_state

    def slow_write(state, snap_dir, **kw):
        assert gate.wait(timeout=30)
        return real_write(state, snap_dir, **kw)

    data, queries = _small_data()
    snap = str(tmp_path / "snap")
    snapmod._write_state = slow_write
    try:
        with AsyncLSHService(_small_index(), bucket_size=8,
                             max_latency_ms=float("inf"),
                             k_neighbors=4) as svc:
            svc.wal = persist.WriteAheadLog(persist.wal_path(snap))
            svc.insert(data[:48]).result(timeout=30)
            path = svc.snapshot(snap).result(timeout=30)
            assert path is not None
            # writer is gated: the next request must skip, not queue
            assert svc.snapshot(snap).result(timeout=30) is None
            assert svc.stats.snapshots == 1
            assert svc.stats.snapshots_skipped == 1
            gate.set()
    finally:
        snapmod._write_state = real_write
    assert os.path.isdir(path)
    assert persist.has_snapshot(snap)
    # the snapshot is the consistent point: recovery replays nothing
    from repro.compat import make_mesh
    rr = persist.recover(snap, make_mesh((1,), ("shard",)),
                         capacity=_small_index().store.capacity,
                         k_neighbors=4)
    assert rr.replayed_inserts == 0 and rr.index.n_live == 48
    rr.wal.close()


def test_engine_survives_poisoned_item():
    """A failing item resolves its own waiters with the error; the
    engine keeps serving subsequent work."""
    from repro.serving import AsyncLSHService

    data, queries = _small_data()
    with AsyncLSHService(_small_index(), bucket_size=8,
                         max_latency_ms=float("inf"),
                         k_neighbors=4) as svc:
        svc.insert(data[:48]).result(timeout=30)
        bad = svc.insert(np.ones((4, 3), np.float32))   # wrong d
        with pytest.raises(Exception):
            bad.result(timeout=30)
        h = svc.submit_batch(queries[:8])
        svc.drain()
        assert all(x.done for x in h)
