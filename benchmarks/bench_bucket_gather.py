"""Bucket-gather benchmark: rows touched per probe + wall time for the
sorted-CSR gather path vs the full-scan kernel, at two corpus sizes.

The tentpole claim this gates: on a bucket-sorted store a probe touches
only its gather window (``G * TILE_N`` rows) instead of the whole padded
corpus, and the reduction GROWS with corpus size (the window is set by
the bucket geometry, not by N).  Acceptance: >= 5x fewer rows touched
per probe at the larger size, with results bitwise identical to the
full scan.

Synthetic single-table store: N points over ~256 uniform buckets,
sorted + CSR via ``store_layout``; R = 1024 queries self-probe their own
row's bucket (L = 1).  The window is sized from the actual spans -- the
same geometry ``DistributedLSHIndex._gather_window`` uses -- and the
no-overflow condition is asserted host-side, so the rows-touched number
is the real kernel footprint, not the fallback's.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import store_layout
from repro.kernels import ops
from repro.kernels.types import QueryBatch, StoreView

TILE_R = TILE_N = 128
N_BUCKETS = 256
R = 1024
D = 32


def _make_case(n: int, seed: int = 0):
    rng = np.random.RandomState(seed)
    points = rng.standard_normal((n, D)).astype(np.float32)
    packed = np.zeros((n, 2), np.int32)
    packed[:, 1] = rng.randint(0, N_BUCKETS, n)
    table = np.zeros(n, np.int32)
    order = store_layout.sort_order(table, packed)
    points, packed = points[order], packed[order]
    bs, be = store_layout.bucket_spans(table, packed)
    store = StoreView.build(
        jnp.asarray(points), jnp.asarray(packed),
        jnp.arange(n, dtype=jnp.int32), jnp.ones(n, jnp.int32),
        bucket_start=jnp.asarray(bs), bucket_end=jnp.asarray(be),
        n_sorted=n)
    qi = rng.randint(0, n, R)
    query = QueryBatch.build(
        jnp.asarray(points[qi] + rng.standard_normal((R, D))
                    .astype(np.float32) * 0.01),
        jnp.asarray(packed[qi]), jnp.ones((R, 1), jnp.int32))
    return query, store, (bs, be, qi)


def _window_tiles(bs: np.ndarray, be: np.ndarray, qi: np.ndarray,
                  n: int) -> int:
    """Smallest G with NO row tile overflowing -- the kernel's own base/
    need math replayed host-side over the sorted probe expansion."""
    start, end = bs[qi].astype(np.int64), be[qi].astype(np.int64)
    order = np.argsort(start, kind="stable")
    start, end = start[order], end[order]
    lo_t = (start // TILE_N).reshape(-1, TILE_R)
    hi_t = ((end - 1) // TILE_N).reshape(-1, TILE_R)
    need = (hi_t.max(1) - lo_t.min(1) + 1).max()
    return int(need)


def _time(f, *args, iters: int = 3) -> float:
    jax.block_until_ready(f(*args))
    t0 = time.monotonic()
    for _ in range(iters):
        jax.block_until_ready(f(*args))
    return (time.monotonic() - t0) / iters


def run_size(n: int, cr2: float = 8.0, k: int = 4) -> dict:
    query, store, (bs, be, qi) = _make_case(n)
    G = _window_tiles(bs, be, qi, n)
    n_tiles = -(-n // TILE_N)
    G = min(G, n_tiles)

    f_csr = jax.jit(lambda q, s: ops.bucket_search(
        query=q, store=s, cr2=cr2, L=1, k=k, window_tiles=G))
    f_full = jax.jit(lambda q, s: ops.bucket_search(
        query=q, store=s, cr2=cr2, L=1, k=k))
    # the baseline: the same rows as an unsorted tail, the full scan
    tail = dataclasses.replace(store, n_sorted=0)

    d_c, g_c, c_c, _ = f_csr(query, store)
    d_f, g_f, c_f, _ = f_full(query, tail)
    np.testing.assert_array_equal(
        np.asarray(d_c).view(np.uint32), np.asarray(d_f).view(np.uint32))
    np.testing.assert_array_equal(np.asarray(g_c), np.asarray(g_f))
    np.testing.assert_array_equal(np.asarray(c_c), np.asarray(c_f))

    t_csr = _time(f_csr, query, store)
    t_full = _time(f_full, query, tail)
    n_pad = n_tiles * TILE_N
    rows_csr = G * TILE_N           # per-probe kernel footprint
    return {
        "n": n, "window_tiles": G,
        "rows_per_probe_sorted": rows_csr,
        "rows_per_probe_full": n_pad,
        "rows_reduction": round(n_pad / rows_csr, 2),
        "query_ms_sorted": round(t_csr * 1e3, 2),
        "query_ms_full": round(t_full * 1e3, 2),
    }


def main(smoke: bool = False) -> dict:
    """Two corpus sizes; returns flat metrics for the CI recorder."""
    sizes = (2048, 16384)
    out: dict = {}
    print("n,window_tiles,rows_sorted,rows_full,reduction,"
          "ms_sorted,ms_full")
    for n in sizes:
        m = run_size(n)
        print(f"{m['n']},{m['window_tiles']},{m['rows_per_probe_sorted']},"
              f"{m['rows_per_probe_full']},{m['rows_reduction']},"
              f"{m['query_ms_sorted']},{m['query_ms_full']}")
        out[f"rows_reduction_n{n}"] = m["rows_reduction"]
        out[f"query_ms_sorted_n{n}"] = m["query_ms_sorted"]
        out[f"query_ms_full_n{n}"] = m["query_ms_full"]
    # the tentpole acceptance claim: the gather's per-probe footprint
    # shrinks relative to the corpus as the corpus grows
    big = sizes[-1]
    assert out[f"rows_reduction_n{big}"] >= 5.0, out
    return out


if __name__ == "__main__":
    main()
