"""The scan step searches only the received rows that can be live.

``scan_body`` keeps one received row per (qid, table), so the S*Cq rows
of the receive buffer are compacted to Rc = min(S*Cq, m*T) rows before
the bucket search:

  * answers are bitwise those of the uncompacted scan, of the full scan
    (``use_csr=False``) and of the jnp oracle (``use_kernel=False``),
    through ``query()`` and ``query_staged()``;
  * the counters show the compaction engaged: the bucket search got
    ceil(Rc*L / TILE_R) * TILE_R expanded rows, with Rc = m*T on one
    and two tables, and Rc = S*Cq under a query capacity below m*T;
    the gather window is still sized from the S*Cq*L expanded rows of
    the whole receive buffer;
  * on four shards (subprocess, placeholder host devices) the same
    holds, and ``query_load`` still counts every valid received row,
    before the dedup and the compaction.
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.core import DistributedLSHIndex, LSHConfig, Scheme
from repro.data import planted_random
from repro.kernels import ops

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COL = {name: i for i, name in enumerate(ops.SCAN_COUNTERS)}
M, L = 48, 8


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


def _assert_same(a, b):
    np.testing.assert_array_equal(_bits(a.topk_dist), _bits(b.topk_dist))
    np.testing.assert_array_equal(a.topk_gid, b.topk_gid)
    np.testing.assert_array_equal(a.n_within_cr, b.n_within_cr)
    np.testing.assert_array_equal(a.fq, b.fq)
    np.testing.assert_array_equal(a.query_load, b.query_load)
    assert a.drops == b.drops


def _index(T, query_capacity, **kw):
    """One shard, a compacted sorted region plus an unsorted tail."""
    cfg = LSHConfig(d=32, k=8, W=1.2, r=0.3, c=2.0, L=L, n_shards=1,
                    scheme=Scheme.LAYERED, seed=0, n_tables=T,
                    query_capacity=query_capacity)
    idx = DistributedLSHIndex(cfg, make_mesh((1,), ("shard",)),
                              k_neighbors=4, **kw)
    data, queries, _ = planted_random(n=512, m=M, d=32, r=0.3, seed=0)
    idx.build(jnp.asarray(data[:448]))
    idx.compact()
    idx.insert(jnp.asarray(data[448:]))
    return idx, jnp.asarray(queries)


@pytest.mark.parametrize("T,query_capacity", [
    pytest.param(1, None, id="one_table"),
    pytest.param(2, None, id="two_tables"),
    pytest.param(1, 32, id="capacity_below_m_T"),
])
def test_compacted_scan_answers_bitwise(T, query_capacity, monkeypatch):
    idx, queries = _index(T, query_capacity, use_kernel=True)
    lay = idx.layout
    assert lay["n_sorted"] > 0 and lay["tail_rows"] > 0
    Cq = idx._query_capacity(M)
    Rc = idx._scan_rows(M, Cq)
    assert Rc == min(Cq, M * T)
    if query_capacity is None:
        assert Rc == M * T < Cq                  # the compaction engages
    else:
        assert Rc == Cq < M * T                  # the row count is kept

    sized = []
    window = idx._gather_window
    monkeypatch.setattr(idx, "_gather_window",
                        lambda n: sized.append(n) or window(n))
    fused = idx.query(queries)
    _assert_same(idx.query_staged(queries), fused)
    # the window is sized from the whole receive buffer, both paths
    assert sized == [Cq * L] * 2
    tr = ops.TILE_R
    c = fused.scan_counters[0]
    assert c[COL["probes_expanded"]] == -(-Rc * L // tr) * tr
    assert 0 < c[COL["probes_live"]] <= Rc * L

    idx.use_csr = False                          # the full-scan kernel
    _assert_same(idx.query(queries), fused)
    idx.use_csr = True
    # the receive buffer searched whole, as before the compaction
    monkeypatch.setattr(idx, "_scan_rows", lambda m, Cq: Cq)
    idx._query_fns.clear()
    whole = idx.query(queries)
    _assert_same(whole, fused)
    if query_capacity is None:
        assert whole.scan_counters[0][COL["probes_expanded"]] == \
            -(-Cq * L // tr) * tr
    oracle, _ = _index(T, query_capacity, use_kernel=False)
    _assert_same(oracle.query(queries), fused)


@pytest.mark.multidevice
def test_compacted_scan_on_four_shards():
    """S = 4: Rc = m*T against S*Cq received rows; answers bitwise those
    of the uncompacted scan, the full scan and the oracle, for the
    layered scheme and for SIMPLE (whose rows of one (qid, table) can
    meet on a shard); ``query_load`` is each shard's valid received
    rows."""
    script = """
    import jax.numpy as jnp, numpy as np
    from repro.compat import make_mesh
    from repro.core import DistributedLSHIndex, LSHConfig, Scheme
    from repro.data import planted_random
    from repro.kernels import ops

    IMAX = np.iinfo(np.int32).max
    S, M, L, T, D = 4, 64, 8, 1, 32
    mesh = make_mesh((S,), ("shard",))
    data, queries, _ = planted_random(n=768, m=M, d=D, r=0.3, seed=0)
    data, queries = jnp.asarray(data), jnp.asarray(queries)

    def same(a, b):
        bits = lambda x: np.asarray(x).view(np.uint32)
        np.testing.assert_array_equal(bits(a.topk_dist), bits(b.topk_dist))
        np.testing.assert_array_equal(a.topk_gid, b.topk_gid)
        np.testing.assert_array_equal(a.n_within_cr, b.n_within_cr)
        np.testing.assert_array_equal(a.fq, b.fq)
        np.testing.assert_array_equal(a.query_load, b.query_load)
        assert a.drops == b.drops

    for scheme in (Scheme.LAYERED, Scheme.SIMPLE):
        cfg = LSHConfig(d=D, k=8, W=1.2, r=0.3, c=2.0, L=L, n_shards=S,
                        scheme=scheme, seed=0, n_tables=T)
        def index(**kw):
            idx = DistributedLSHIndex(cfg, mesh, k_neighbors=4, **kw)
            idx.build(data[:640])
            idx.compact()
            idx.insert(data[640:])
            return idx
        idx = index(use_kernel=True)
        Cq = idx._query_capacity(M // S)
        Rc = idx._scan_rows(M, Cq)
        assert Rc == M * T < S * Cq, (Rc, S * Cq)
        fused = idx.query(queries)
        same(idx.query_staged(queries), fused)
        tr = ops.TILE_R
        col = ops.SCAN_COUNTERS.index("probes_expanded")
        assert (fused.scan_counters[:, col]
                == -(-Rc * L // tr) * tr).all(), fused.scan_counters

        # query_load: every valid row each shard received, before the
        # dedup and the compaction (the paper's load)
        rid = np.asarray(idx.query_dispatch(queries).recv)[:, D]
        per_shard = (rid != IMAX).reshape(S, S * Cq).sum(axis=1)
        np.testing.assert_array_equal(fused.query_load, per_shard)
        assert fused.query_load.sum() == fused.fq.sum()

        idx.use_csr = False
        same(idx.query(queries), fused)
        idx.use_csr = True
        idx._scan_rows = lambda m, Cq: S * Cq     # the whole buffer
        idx._query_fns.clear()
        whole = idx.query(queries)
        same(whole, fused)
        assert (whole.scan_counters[:, col]
                == -(-S * Cq * L // tr) * tr).all(), whole.scan_counters
        same(index(use_kernel=False).query(queries), fused)
        print("OK", scheme.name, fused.query_load.tolist())
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert out.stdout.count("OK") == 2, out.stdout
