"""Gather-by-table hashing tests (the StackedHashParams tentpole contract)
plus the serving/delete edge-case fixes that ride along.

  * stacking per-table ``HashParams`` preserves every field bitwise and
    the per-table views round-trip;
  * the dispatch-side broadcast (one vmap over the stacked T axis) and
    the receive-side gather (``params[table]`` per row, hash once)
    reproduce the per-table LOOPED hash path BIT-FOR-BIT: at T=1 this is
    the pre-change parity contract (gathering table 0's A then matmuling
    is reduction-order-identical to hashing under the plain single-table
    params), at T in {2, 4} it is the looped-vs-gathered equivalence
    property the refactor must satisfy;
  * gathered offsets (``query_offsets_by_table``) equal the looped
    ``query_offsets`` bitwise, including the vmapped fold_in/normal RNG;
  * the compiled query-step jaxpr is FLAT in T (subprocess, 8 devices)
    instead of the old linear growth;
  * a failed ``ShardedLSHService.flush`` requeues the handles WITH their
    original latency deadline and ``result()`` still resolves;
  * ``insert(gids=...)`` / ``delete()`` reject gids >= IMAX and negative
    gids instead of silently aliasing the IMAX padding sentinel.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (LSHConfig, Scheme, StackedHashParams, hash_h,
                        pack_buckets, query_offsets, query_offsets_by_table,
                        sample_stacked_params, sample_table_params,
                        shard_key, stacked_base_keys, table_base_key)
from repro.kernels.ops import SCAN_COUNTERS

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
IMAX = int(np.iinfo(np.int32).max)


def _cfg(T, **kw):
    base = dict(d=50, k=10, W=1.2, r=0.3, c=2.0, L=16, n_shards=8,
                scheme=Scheme.LAYERED, seed=0, n_tables=T)
    base.update(kw)
    return LSHConfig(**base)


def _bits(x):
    """Bit view for exact float comparison (ints compare as-is)."""
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


def _assert_bitwise(a, b, msg=""):
    np.testing.assert_array_equal(_bits(a), _bits(b), err_msg=msg)


# ---------------------------------------------------------------------------
# Stacking round-trip
# ---------------------------------------------------------------------------

def test_stack_preserves_tables_bitwise():
    """stack() then table(t) returns every per-table field bit-for-bit,
    and the stacked values equal sample_stacked_params directly."""
    cfg = _cfg(4)
    key = jax.random.PRNGKey(cfg.seed)
    tables = sample_table_params(key, cfg)
    stacked = StackedHashParams.stack(tables)
    direct = sample_stacked_params(key, cfg)
    assert stacked.n_tables == 4
    for t, p in enumerate(tables):
        for f in dataclasses.fields(p):
            _assert_bitwise(getattr(stacked.table(t), f.name),
                            getattr(p, f.name), msg=f"table {t} {f.name}")
            _assert_bitwise(getattr(direct, f.name)[t],
                            getattr(p, f.name), msg=f"direct {t} {f.name}")


def test_stacked_base_keys_match_table_base_key():
    base = jax.random.PRNGKey(7)
    skeys = stacked_base_keys(base, 4)
    for t in range(4):
        _assert_bitwise(skeys[t], table_base_key(base, t))


# ---------------------------------------------------------------------------
# Looped vs gathered equivalence (T in {2, 4}) and T=1 bitwise parity
# ---------------------------------------------------------------------------

def _keys_of(p, offs, cfg):
    """The index's per-row hash body: offsets (L, d) -> keys + packed."""
    hk = hash_h(p, offs, cfg.W)
    return shard_key(p, cfg, hk), pack_buckets(p, hk)


@pytest.mark.parametrize("T", [1, 2, 4])
def test_dispatch_broadcast_matches_loop_bitwise(T):
    """The insert dispatch's single vmapped hash pass (params broadcast
    over the stacked T axis) equals the per-table Python loop bitwise.
    At T=1 this is exactly the pre-change single-table hash stream."""
    cfg = _cfg(T)
    stacked = sample_stacked_params(jax.random.PRNGKey(cfg.seed), cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (37, cfg.d), jnp.float32)

    def hash_table(p):
        hk = hash_h(p, x, cfg.W)
        return (pack_buckets(p, hk),
                jnp.mod(shard_key(p, cfg, hk), cfg.n_shards))

    packs, dests = jax.jit(jax.vmap(hash_table))(stacked)
    for t in range(T):
        p = stacked.table(t)
        hk = hash_h(p, x, cfg.W)                 # the looped/old path
        _assert_bitwise(packs[t], pack_buckets(p, hk), msg=f"packed t={t}")
        _assert_bitwise(dests[t], jnp.mod(shard_key(p, cfg, hk),
                                          cfg.n_shards), msg=f"dest t={t}")


@pytest.mark.parametrize("T", [1, 2, 4])
def test_receive_gather_matches_loop_bitwise(T):
    """The receive side's gather-then-hash-once pass (params[table] per
    row) equals hashing every row under ALL T tables and where-selecting
    its own -- the old looped formulation -- bitwise, offsets included."""
    cfg = _cfg(T)
    stacked = sample_stacked_params(jax.random.PRNGKey(cfg.seed), cfg)
    skeys = stacked_base_keys(jax.random.PRNGKey(11), T)
    R = 53
    rng = np.random.RandomState(0)
    rtab = jnp.asarray(rng.randint(0, T, R), jnp.int32)
    rid = jnp.asarray(rng.randint(0, 1000, R), jnp.int32)
    rq = jax.random.normal(jax.random.PRNGKey(5), (R, cfg.d), jnp.float32)

    # gathered path (what query_shard now runs).  Eager on both sides:
    # bitwise identity is a property of the batched PRIMITIVES (gathered
    # dot_general / elementwise ops == looped ones); jit-level fusion may
    # legally reassociate floats differently between compilation units,
    # which the end-to-end exact-agreement tests cover instead.
    roffs = query_offsets_by_table(skeys, rtab, rid, rq, cfg.L, cfg.r)
    rkey, rpacked = jax.vmap(
        lambda p, o: _keys_of(p, o, cfg))(stacked.gather(rtab), roffs)

    # looped reference: per-table offsets/keys, where-select by table id
    for i in range(R):
        t = int(rtab[i])
        offs = query_offsets(skeys[t], rid[i], rq[i], cfg.L, cfg.r)
        keyv, packed = _keys_of(stacked.table(t), offs, cfg)
        _assert_bitwise(roffs[i], offs, msg=f"offsets row {i}")
        _assert_bitwise(rkey[i], keyv, msg=f"keys row {i}")
        _assert_bitwise(rpacked[i], packed, msg=f"packed row {i}")


def test_t1_gather_is_identity_bitwise():
    """T=1 pre-change parity: gathering table 0's params then hashing is
    bit-for-bit the plain single-table path (reduction-order-identical
    matmuls), for both the first and second hash layers."""
    cfg = _cfg(1)
    stacked = sample_stacked_params(jax.random.PRNGKey(cfg.seed), cfg)
    plain = stacked.table(0)
    offs = jax.random.normal(jax.random.PRNGKey(9), (64, cfg.L, cfg.d),
                             jnp.float32)
    tids = jnp.zeros((64,), jnp.int32)
    gkey, gpacked = jax.jit(jax.vmap(
        lambda p, o: _keys_of(p, o, cfg)))(stacked.gather(tids), offs)
    pkey, ppacked = jax.jit(jax.vmap(
        lambda o: _keys_of(plain, o, cfg)))(offs)
    _assert_bitwise(gkey, pkey)
    _assert_bitwise(gpacked, ppacked)


# ---------------------------------------------------------------------------
# Compiled query step: jaxpr size flat in T (subprocess, 8 devices)
# ---------------------------------------------------------------------------

@pytest.mark.multidevice
def test_query_jaxpr_size_flat_in_tables():
    """The acceptance criterion for the gather refactor: the query-step
    (and insert-step) jaxpr no longer grows linearly in T.  Counted
    structurally via the analyzer; the ceiling is the single manifest
    flatness ratio (contracts.json), not a local constant."""
    script = """
    import jax, numpy as np
    import jax.numpy as jnp
    from repro.analysis import jaxpr_pass, load_contracts
    from repro.analysis.check import query_stages
    from repro.compat import make_mesh
    from repro.core import LSHConfig, Scheme, DistributedLSHIndex
    from repro.data import planted_random

    ratio = load_contracts()["jaxpr"]["flatness"]["max_ratio"]
    mesh = make_mesh((8,), ("shard",))
    data, queries, _ = planted_random(n=512, m=64, d=32, r=0.3, seed=0)
    data, queries = jnp.asarray(data), jnp.asarray(queries)
    q_eqns, i_eqns = {}, {}
    for T in (1, 2, 4):
        cfg = LSHConfig(d=32, k=8, W=1.2, r=0.3, c=2.0, L=8, n_shards=8,
                        scheme=Scheme.LAYERED, seed=0, n_tables=T)
        idx = DistributedLSHIndex(cfg, mesh)
        idx.build(data)
        st = idx.store
        # a query is its three staged programs: count them together
        q_eqns[T] = sum(
            jaxpr_pass.eqn_count(jax.make_jaxpr(fn)(*args))
            for _, fn, args in query_stages(idx, queries[:64], 4, G=4))
        n_loc = 64 // 8
        inf = idx._make_insert_fn(n_loc, idx._dispatch_capacity(n_loc * T),
                                  st.capacity, st.n_sorted)
        i_eqns[T] = jaxpr_pass.eqn_count(jax.make_jaxpr(inf)(
            data[:64], jnp.arange(64, dtype=jnp.int32), jnp.ones(64, bool),
            st.x, st.packed, st.gid, st.table, st.key, st.valid))
    print("query jaxpr eqns:", q_eqns, "insert:", i_eqns)
    # flat, not linear (the old looped path was ~T x larger)
    assert not jaxpr_pass.check_flatness(q_eqns, ratio, "query"), q_eqns
    assert not jaxpr_pass.check_flatness(i_eqns, ratio, "insert"), i_eqns
    print("OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "OK" in out.stdout


# ---------------------------------------------------------------------------
# Serving: a failed flush keeps the latency deadline on the requeue path
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _FakeCfg:
    n_shards: int = 1
    d: int = 8


class _FakeIndex:
    """Minimal index stub: query() fails on demand, else returns empties."""

    def __init__(self):
        self.cfg = _FakeCfg()
        self.k_neighbors = 1
        self.fail = False
        self.calls = 0

    def query(self, qs, donate=False, k_neighbors=None, batch=None):
        self.calls += 1
        if self.fail:
            raise RuntimeError("injected query-step failure")
        b = qs.shape[0]
        K = k_neighbors or 1
        return dataclasses.make_dataclass("R", [
            "topk_dist", "topk_gid", "n_within_cr", "fq", "query_load",
            "drops", "scan_counters"])(
                topk_dist=np.full((b, K), np.inf, np.float32),
                topk_gid=np.full((b, K), IMAX, np.int32),
                n_within_cr=np.zeros((b,), np.int64),
                fq=np.zeros((b,), np.int64),
                query_load=np.zeros((1,), np.int64), drops=0,
                scan_counters=np.zeros((1, len(SCAN_COUNTERS)), np.int32))


def test_flush_failure_requeues_with_original_deadline():
    """A failed query step requeues the handles AND restores the latency
    deadline that was already advanced before the exception -- the
    requeued queries keep their SLO without waiting for a fresh submit."""
    from repro.serving import ShardedLSHService
    fake = _FakeIndex()
    # a frozen clock: the deadline cannot lapse between two calls, however
    # slowly a loaded test worker runs them
    svc = ShardedLSHService(fake, bucket_size=4, max_latency_ms=50.0,
                            clock=lambda: 100.0)
    h = svc.submit(np.zeros(8, np.float32))
    d0 = svc._deadline
    assert d0 is not None

    fake.fail = True
    with pytest.raises(RuntimeError, match="injected"):
        svc.flush()
    # handle requeued, deadline RESTORED (the bug cleared it to None)
    assert svc.n_pending == 1 and not h.done
    assert svc._deadline == d0
    assert svc.stats.queries == 0 and svc.stats.batches == 0

    # a later submit must still see the ORIGINAL (not a fresh) deadline
    h2 = svc.submit(np.ones(8, np.float32))
    assert svc._deadline == d0

    fake.fail = False
    r = h.result()
    assert r.done and h2.done and svc.n_pending == 0
    assert svc._deadline is None
    assert svc.stats.queries == 2


def test_full_bucket_flush_failure_mid_submit_keeps_deadline():
    """A full-bucket auto-flush that fails inside submit_batch requeues
    the bucket at the FRONT with the oldest query's deadline restored,
    and a later recovered flush drains in submission order."""
    from repro.serving import ShardedLSHService
    fake = _FakeIndex()
    now = [100.0]                             # test-driven clock
    svc = ShardedLSHService(fake, bucket_size=4, max_latency_ms=1e4,
                            clock=lambda: now[0])
    h1 = svc.submit_batch(np.zeros((3, 8), np.float32))
    d0 = svc._deadline
    fake.fail = True
    with pytest.raises(RuntimeError, match="injected"):
        svc.submit(np.zeros(8, np.float32))   # 4th query -> full flush
    assert svc.n_pending == 4                 # whole bucket requeued
    assert svc._deadline == d0                # oldest query keeps its SLO

    fake.fail = False
    now[0] += 1.0                             # well inside the 10 s SLO
    svc.submit_batch(np.zeros((2, 8), np.float32))  # 6th -> flush fires
    assert all(h.done for h in h1)            # oldest bucket went first
    assert svc.n_pending == 2
    assert svc._deadline is not None and svc._deadline != d0
    assert svc.drain() == 2
    assert svc.stats.queries == 6


# ---------------------------------------------------------------------------
# Out-of-range gids are rejected (IMAX aliases the padding sentinel)
# ---------------------------------------------------------------------------

def _tiny_index():
    from repro.compat import make_mesh
    from repro.core import DistributedLSHIndex
    cfg = LSHConfig(d=8, k=4, W=1.0, r=0.3, c=2.0, L=4, n_shards=1,
                    scheme=Scheme.LAYERED, seed=0)
    return DistributedLSHIndex(cfg, make_mesh((1,), ("shard",)))


def test_param_assignment_rejected_on_populated_store():
    """Swapping table params/keys after rows were routed under the old
    ones would silently probe stale buckets -- assignment must raise once
    the store exists (and still work before build/insert)."""
    idx = _tiny_index()
    # canonical stacked accessors: no warning, pre-store assignment allowed
    idx.stacked_params = idx.stacked_params
    idx.stacked_keys = idx.stacked_keys
    # deprecated per-table shims still delegate (and warn)
    with pytest.warns(DeprecationWarning):
        idx.table_params = idx.table_params      # pre-store: allowed
    with pytest.warns(DeprecationWarning):
        idx.table_keys = idx.table_keys
    idx.insert(np.zeros((4, 8), np.float32))
    with pytest.raises(RuntimeError, match="populated"):
        idx.stacked_params = idx.stacked_params
    with pytest.raises(RuntimeError, match="populated"):
        idx.stacked_keys = idx.stacked_keys
    with pytest.warns(DeprecationWarning), \
            pytest.raises(RuntimeError, match="populated"):
        idx.table_params = idx.table_params
    with pytest.warns(DeprecationWarning), \
            pytest.raises(RuntimeError, match="populated"):
        idx.table_keys = idx.table_keys


def test_insert_rejects_out_of_range_gids():
    idx = _tiny_index()
    pts = np.zeros((2, 8), np.float32)
    with pytest.raises(ValueError, match="gids"):
        idx.insert(pts, gids=[0, IMAX])          # == sentinel
    with pytest.raises(ValueError, match="gids"):
        idx.insert(pts, gids=[0, IMAX + 1])      # > sentinel (would wrap)
    with pytest.raises(ValueError, match="gids"):
        idx.insert(pts, gids=[-1, 3])            # negative
    # boundary value IMAX-1 is legal and stored
    r = idx.insert(pts, gids=np.asarray([5, IMAX - 1], np.int64))
    assert r.n_inserted == 2 and r.gid_start == 5
    # ... but the auto-gid counter now sits AT the sentinel, so the next
    # auto-gid batch must be rejected too (it would mint gid == IMAX and
    # wrap int32 beyond it) instead of silently aliasing padding
    with pytest.raises(ValueError, match="auto-gid"):
        idx.insert(pts)


def test_delete_rejects_out_of_range_gids():
    idx = _tiny_index()
    idx.insert(np.zeros((4, 8), np.float32))
    for bad in ([IMAX], [IMAX + 7], [-2], [3, IMAX]):
        with pytest.raises(ValueError, match="gids"):
            idx.delete(bad)
    assert idx.delete([0, 3]).n_deleted == 2     # in-range still works
