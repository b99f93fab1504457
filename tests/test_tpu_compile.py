"""Compile the served path's Pallas kernels for a TPU v5e that is described,
not attached: what Mosaic refuses (misaligned blocks, layouts, VMEM) fails
here, in the CPU test lane, instead of on the chip.

The topology is described inside a module fixture (never at import): only
the worker that runs these tests loads the TPU compiler library.  The
persistent compilation cache is off around the compiles -- entries for a
described chip cannot be read back without one.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels.bucket_search import (bucket_gather_pallas,
                                         bucket_search_pallas)
from repro.kernels.types import QueryBatch, StoreView

L = 16
R = N = 1024      # rows and points: the kernels compile per tile, not per N


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")    # no compiler logs outside
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()
        mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _hlo(fn, *args, **kwargs) -> str:
    return fn.lower(*args, **kwargs).compile().as_text()


@pytest.mark.parametrize("K", [10, 100])
@pytest.mark.parametrize("d", [96, 128, 768])
def test_bucket_search_compiles_for_v5e(one_chip, d, K):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    i32 = jnp.int32
    query = QueryBatch(q=s((R, d), jnp.float32), qsq=s((R,), jnp.float32),
                       buckets=s((R, 2 * L), i32), probe=s((R, L), i32),
                       table=s((R,), i32))
    store = StoreView(points=s((N, d), jnp.float32),
                      psq=s((N,), jnp.float32), buckets=s((N, 2), i32),
                      gid=s((N,), i32), valid=s((N,), i32),
                      table=s((N,), i32))
    hlo = _hlo(bucket_search_pallas, query=query, store=store, cr2=1.0,
               L=L, K=K)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("K", [10, 100])
@pytest.mark.parametrize("d", [96, 128, 768])
def test_bucket_gather_compiles_for_v5e(one_chip, d, K):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    i32, f32 = jnp.int32, jnp.float32
    hlo = _hlo(bucket_gather_pallas, s((R // 128,), i32), s((R, d), f32),
               s((R,), f32), s((R,), i32), s((R,), i32), s((N, d), f32),
               s((N,), f32), s((N,), i32), s((N,), i32), 1.0, K=K, G=4)
    assert "tpu_custom_call" in hlo


def test_index_query_scan_compiles_for_v5e_mesh(topo, monkeypatch):
    """The index's whole per-shard scan step (span search, CSR gather,
    tail full scan, local merge) on a described 4-chip mesh: the served
    path reaches the Mosaic kernels, with no interpreter and no oracle."""
    from repro.core import DistributedLSHIndex, LSHConfig, Scheme
    from repro.kernels import ops as kops
    # the index asks the default backend (the CPU here) whether to
    # interpret the kernels; the described chip does not
    monkeypatch.setattr(kops, "_on_cpu", lambda: False)
    S, d, cap, ns, m = 4, 128, 2048, 1024, 64
    mesh = Mesh(np.array(topo.devices[:S]), ("shard",))
    cfg = LSHConfig(d=d, k=10, W=3.0, r=0.3, c=5.0, L=L, n_shards=S,
                    scheme=Scheme.LAYERED)
    idx = DistributedLSHIndex(cfg, mesh, use_kernel=True, k_neighbors=10)
    Cq = idx._query_capacity(m // S)
    sh = NamedSharding(mesh, P("shard"))
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    i32 = jnp.int32
    fn = idx._make_query_scan_fn(m, cap, Cq, 10, ns, 4)
    hlo = _hlo(fn, s((S * S * Cq, d + 2), i32), s((S, cap, d), jnp.float32),
               s((S, cap, 2), jnp.uint32), s((S, cap), i32),
               s((S, cap), i32), s((S, cap), jnp.bool_), s((S, cap), i32),
               s((S, cap), i32))
    # both kernels: the CSR gather over the sorted region, the full scan
    # over the tail (and as the overflow fallback)
    assert hlo.count("tpu_custom_call") >= 2, hlo.count("tpu_custom_call")
    # ... over the received rows compacted to m*T (64 of S*Cq = 580):
    # one row tile for the full scans, m*T*L expanded rows for the gather
    Rc = idx._scan_rows(m, Cq)
    assert Rc == m < S * Cq, (Rc, S * Cq)
    rows = {name: int(n) for name, n in re.findall(
        r"%(bucket_\w+?)(?:\.\d+)? = \(f32\[(\d+),", hlo)}
    assert rows == {"bucket_gather_pallas": Rc * L,
                    "bucket_search_pallas_tail": 128,
                    "bucket_search_pallas_fallback": 128}, rows
