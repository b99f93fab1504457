"""Fused multi-table index tests (tentpole acceptance contract).

  * recall@K is monotone non-decreasing in n_tables on the simulator
    (tables are a nested prefix sequence, so the union candidate set only
    grows) -- single-device, no mesh;
  * the fused T-table distributed query equals (a) the single-machine
    union reference and (b) the host-side union-merge of T independent
    single-table indexes running the same per-table params/offset keys;
  * a compiled-trace (jaxpr) test proves insert/query/return issue
    exactly ONE cross-shard collective each (insert: 1 fused all_to_all;
    query: dispatch a2a + routed-return a2a; NO all_gather, NO psum) for
    any T in {1, 2, 4};
  * InsertResult.gid_start reports the batch's actual minimum gid (or
    None for an empty batch) for explicit gids too.
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


COMMON = """
import dataclasses
import jax, numpy as np
import jax.numpy as jnp
from repro.compat import make_mesh
from repro.core import LSHConfig, Scheme, DistributedLSHIndex
from repro.core.hashing import StackedHashParams
from repro.data import planted_random

def cfg_t(T, **kw):
    base = dict(d=50, k=10, W=1.2, r=0.3, c=2.0, L=16, n_shards=8,
                scheme=Scheme.LAYERED, seed=0, n_tables=T)
    base.update(kw)
    return LSHConfig(**base)

mesh = make_mesh((8,), ("shard",))
data, queries, planted = planted_random(n=2048, m=256, d=50, r=0.3, seed=0)
data, queries = jnp.asarray(data), jnp.asarray(queries)
"""


# ---------------------------------------------------------------------------
# Simulator: recall monotone in T (single device, fast lane)
# ---------------------------------------------------------------------------

def test_recall_monotone_in_tables():
    """Union candidates only grow with T (nested table prefix), so both
    the paper's recall and recall@K are monotone non-decreasing."""
    from repro.core import LSHConfig, Scheme, simulate
    from repro.data import planted_random
    data, queries, _ = planted_random(n=2048, m=256, d=50, r=0.3, seed=0)
    data, queries = jnp.asarray(data), jnp.asarray(queries)
    prev_recall, prev_rk, prev_rows = -1.0, -1.0, -1
    t0_rows = None
    for T in (1, 2, 4):
        cfg = LSHConfig(d=50, k=10, W=1.2, r=0.3, c=2.0, L=16, n_shards=8,
                        scheme=Scheme.LAYERED, seed=0, n_tables=T)
        rep = simulate(cfg, data, queries, compute_recall=True,
                       k_neighbors=10)
        assert rep.recall >= prev_recall
        assert rep.recall_at_k >= prev_rk
        assert rep.query_rows > prev_rows    # more tables, more rows ...
        assert rep.collectives_query == 2    # ... same collectives
        assert rep.collectives_insert == 1
        # nested prefix: table 0 traffic identical at every T
        if t0_rows is None:
            t0_rows = rep.query_rows_by_table[0]
        assert rep.query_rows_by_table[0] == t0_rows
        assert len(rep.query_rows_by_table) == T
        prev_recall, prev_rk, prev_rows = (rep.recall, rep.recall_at_k,
                                           rep.query_rows)
    # the sweep must actually exercise the lever on this dataset
    assert prev_recall > 0.0


# ---------------------------------------------------------------------------
# first_occurrence_mask: the sort-based replacement for the O(R^2) dedup
# ---------------------------------------------------------------------------

def test_first_occurrence_mask_matches_pairwise():
    from repro.core import first_occurrence_mask
    rng = np.random.RandomState(0)
    for trial in range(5):
        R = 257
        keys = rng.randint(0, 40, size=R).astype(np.int32)
        valid = rng.rand(R) < 0.7
        got = np.asarray(first_occurrence_mask(jnp.asarray(keys),
                                               jnp.asarray(valid)))
        # oracle: first live row of each key in index order
        seen, want = set(), np.zeros(R, bool)
        for i in range(R):
            if valid[i] and keys[i] not in seen:
                seen.add(keys[i])
                want[i] = True
        np.testing.assert_array_equal(got, want)


def test_first_occurrence_mask_all_invalid():
    from repro.core import first_occurrence_mask
    keys = jnp.zeros((16,), jnp.int32)
    valid = jnp.zeros((16,), bool)
    assert not np.asarray(first_occurrence_mask(keys, valid)).any()


# ---------------------------------------------------------------------------
# Distributed fused index (subprocess, 8 host devices)
# ---------------------------------------------------------------------------

@pytest.mark.multidevice
def test_fused_equals_union_of_single_tables():
    """The fused T-table query must equal the host-side union-merge of T
    independent single-table indexes running the same per-table params
    and offset keys, AND the single-machine union reference."""
    out = _run(COMMON + """
from repro.core import lsh_topk_reference

K, T = 10, 3
fused_cfg = cfg_t(T)
fused = DistributedLSHIndex(fused_cfg, mesh, k_neighbors=K)
fused.build(data)
qr = fused.query(queries)
assert qr.drops == 0

# (a) single-machine union reference: exact agreement
refd, refg = lsh_topk_reference(fused_cfg, data, queries, K)
np.testing.assert_array_equal(qr.topk_gid, refg)

# (b) T independent single-table indexes with the SAME per-table keys
per_table = []
for t in range(T):
    idx = DistributedLSHIndex(cfg_t(1), mesh, k_neighbors=K)
    idx.stacked_params = StackedHashParams.stack(
        [fused.stacked_params.table(t)])
    idx.stacked_keys = fused.stacked_keys[t][None]
    idx.build(data)
    rt = idx.query(queries)
    assert rt.drops == 0
    per_table.append(rt)

m = queries.shape[0]
imax = np.iinfo(np.int32).max
union_g = np.full((m, K), imax, np.int32)
union_d = np.full((m, K), np.inf, np.float32)
for i in range(m):
    cand = {}
    for rt in per_table:
        for dist, gid in zip(rt.topk_dist[i], rt.topk_gid[i]):
            if gid != imax and (gid not in cand or dist < cand[gid]):
                cand[int(gid)] = float(dist)
    top = sorted(((d, g) for g, d in cand.items()))[:K]
    for j, (d, g) in enumerate(top):
        union_d[i, j] = d
        union_g[i, j] = g
np.testing.assert_array_equal(qr.topk_gid, union_g)
fin = np.isfinite(union_d)
np.testing.assert_allclose(qr.topk_dist[fin], union_d[fin],
                           rtol=1e-6, atol=1e-6)
# emit counts sum per table
total_emit = sum(rt.n_within_cr for rt in per_table)
np.testing.assert_array_equal(qr.n_within_cr, total_emit)
# fq sums per table
total_fq = sum(rt.fq for rt in per_table)
np.testing.assert_array_equal(qr.fq, total_fq)
print("OK")
""")
    assert "OK" in out


@pytest.mark.multidevice
def test_collective_count_independent_of_tables():
    """Compiled-trace proof: one fused all_to_all for insert, exactly two
    for a query (one in its dispatch step, one in its routed return, none
    in its scan), zero all_gather/psum -- for any T.  This is the
    acceptance criterion for the one-collective-per-phase refactor."""
    out = _run(COMMON + """
from collections import Counter
from repro.analysis import jaxpr_pass, load_contracts
from repro.analysis.check import query_stages

budgets = load_contracts()["jaxpr"]["collectives"]

for T in (1, 2, 4):
    cfg = cfg_t(T, d=32, k=8, L=8)
    idx = DistributedLSHIndex(cfg, mesh)
    idx.build(data[:512, :32])
    st = idx.store
    n_loc = 64 // 8
    ins = idx._make_insert_fn(n_loc, idx._dispatch_capacity(n_loc * T),
                              st.capacity, st.n_sorted)
    c = jaxpr_pass.collective_counts(jax.make_jaxpr(ins)(
        data[:64, :32], jnp.arange(64, dtype=jnp.int32),
        jnp.ones(64, bool), st.x, st.packed, st.gid, st.table, st.key,
        st.valid))
    # structural, exact-match: one fused a2a, every other kind zero
    assert not jaxpr_pass.check_collectives(c, budgets["insert"]), (T, c)
    assert c == {"all_to_all": 1}, (T, c)

    stages = []
    for phase, fn, args in query_stages(idx, queries[:64, :32], 4, G=4):
        c = jaxpr_pass.collective_counts(jax.make_jaxpr(fn)(*args))
        assert not jaxpr_pass.check_collectives(c, budgets[phase]), (T, c)
        stages.append(c)
    assert stages == [{"all_to_all": 1}, {}, {"all_to_all": 1}], (T, stages)
    # the paper's invariant: two all_to_all per query, at every T
    assert sum(map(Counter, stages), Counter()) == {"all_to_all": 2}
print("OK")
""")
    assert "OK" in out


@pytest.mark.multidevice
def test_multi_table_streaming_and_delete():
    """Streaming semantics survive fusion: build == build+insert at T=2,
    delete tombstones all T copies, and the service threads multi-table
    queries unchanged."""
    out = _run(COMMON + """
from repro.serving import ShardedLSHService

cfg = cfg_t(2)
idx = DistributedLSHIndex(cfg, mesh)
br = idx.build(data)
qr = idx.query(queries)
assert br.drops == 0 and idx.n_live == 2048 * 2

idx2 = DistributedLSHIndex(cfg, mesh)
idx2.build(data[:1024])
ir = idx2.insert(data[1024:])
assert ir.drops == 0 and ir.n_inserted == 1024 and ir.rows_stored == 2048
qr2 = idx2.query(queries)
np.testing.assert_array_equal(qr2.topk_gid, qr.topk_gid)
np.testing.assert_array_equal(qr2.n_within_cr, qr.n_within_cr)
np.testing.assert_array_equal(idx2._shard_load, br.data_load)

# delete removes BOTH table copies
victims = np.unique(qr.topk_gid[:, 0][np.isfinite(qr.topk_dist[:, 0])])[:10]
dr = idx.delete(victims)
assert dr.n_deleted == 2 * len(victims), dr.n_deleted
qr3 = idx.query(queries)
assert not np.isin(qr3.topk_gid, victims).any()

# service front-end over the fused index
svc = ShardedLSHService(idx2, bucket_size=64, k_neighbors=5)
handles = svc.submit_batch(np.asarray(queries[:64])); svc.drain()
qb = idx2.query(queries[:64], k_neighbors=5)
np.testing.assert_array_equal(
    np.stack([h.gids for h in handles]), qb.topk_gid)
assert svc.stats.collectives_issued == 2  # one flush = dispatch + return
print("OK")
""")
    assert "OK" in out


@pytest.mark.multidevice
def test_gid_start_reports_batch_minimum():
    """InsertResult.gid_start is the batch's min gid for explicit gids
    (not the unrelated pre-call counter), and None for empty batches."""
    out = _run(COMMON + """
idx = DistributedLSHIndex(cfg_t(1), mesh)
r1 = idx.insert(data[:64])                       # auto gids 0..63
assert r1.gid_start == 0
r2 = idx.insert(data[64:128])                    # auto gids 64..127
assert r2.gid_start == 64
r3 = idx.insert(data[128:192],
                gids=np.arange(1000, 1064, dtype=np.int32))
assert r3.gid_start == 1000, r3.gid_start        # batch min, not 128
r4 = idx.insert(data[192:256],
                gids=np.arange(500, 564, dtype=np.int32))
assert r4.gid_start == 500, r4.gid_start         # even below _next_gid
r5 = idx.insert(data[:0])
assert r5.gid_start is None and r5.n_inserted == 0
print("OK")
""")
    assert "OK" in out
