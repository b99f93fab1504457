"""Pallas TPU kernels: streaming bucket-constrained top-K neighbour scan.

The Reduce/UDF inner loop of the paper (Fig 3.2): for every received query
row, find the K closest stored points among those whose packed H-bucket
matches one of the query's *probed* offset buckets, subject to the
distance threshold (cr)^2.

Two kernels share one accumulator design:

  * ``bucket_search_pallas`` -- the FULL SCAN: every (row tile, point
    tile) pair is visited and the bucket-equality mask selects matches.
    O(N) point tiles per row tile, but layout-agnostic: it is the path
    for unsorted stores and for the insert tail.
  * ``bucket_gather_pallas`` -- the CSR GATHER: the store is sorted by
    (table, bucket) and each expanded (query row, probe) carries its
    bucket's CSR span [start, end).  A scalar-prefetched per-row-tile
    base index steers the point-tile BlockSpec, so only the G aligned
    store tiles covering the tile's spans are streamed -- O(bucket
    occupancy) work per probe instead of O(N_shard).

Fusion story (both kernels): the (TILE_R, TILE_N) pairwise-distance tile
comes off the MXU (via -2 Q P^T plus norm epilogue), and the mask, the
threshold filter and the running top-K reduction all happen in the same
VMEM residency -- the O(R*N) distance matrix never reaches HBM.  The
accumulator is a per-row (dist^2, gid) list of length K kept sorted by
(dist^2, gid) lex order in the revisited output blocks.  A point tile is
merged in by insertion: only the tile's pairs lex-smaller than the row's
running K-th pair can enter, and the tile runs one pass per such pair of
its busiest row (at most K, none on a tile that improves no row).  Pass j
picks each row's j-th smallest entering pair and inserts it at its rank,
shifting the accumulator lanes above it by one -- O(TILE_N + KP) VPU work
per pass, no sort network.  Each kernel also returns the passes it ran,
summed over its grid.

Mosaic layout (what the TPU compiler accepts): every block is 2-D.
Per-row scalars travel as (R, 1) columns, per-point scalars as
lane-dense (1, N) rows, probe buckets as separate hi/lo (R, L) words,
and the accumulators as (R, KP) blocks with KP = K rounded up to the
128-lane width (lanes >= K hold sentinels and are sliced off by the
wrapper).  The distance matmul runs at HIGHEST precision, so the f32
contract is the same on the chip as in the CPU interpreter.

Because both kernels feed the SAME (TILE_R, d) x (TILE_N, d) dot_general
with identical aligned point tiles, and the insertion merge is exact
selection over lex (dist^2, gid) order (visit-order independent), the
gather kernel's results are bitwise identical to the full scan's (the
pass counts depend on the visit order).

Grid: (row tiles, point tiles); the point axis is minor-most, so the
output blocks for a row tile are revisited across point tiles and act as
the running accumulator (standard TPU streaming-reduction pattern).  The
full scan's point axis may stop short of the store (``n_tiles``): the
grid, not the arrays, is bounded.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.types import QueryBatch, StoreView

TILE_R = 128
TILE_N = 128
LANES = 128
F32_MAX = float(jnp.finfo(jnp.float32).max)
IMAX = int(jnp.iinfo(jnp.int32).max)
_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def k_lanes(K: int) -> int:
    """Accumulator width: K rounded up to whole 128-lane vregs."""
    return -(-K // LANES) * LANES


def _sq_dist(q_ref, qsq_ref, p_ref, psq_ref):
    """(TR, TN) squared distances from the MXU, clamped at 0."""
    q = q_ref[...].astype(jnp.float32)            # (TR, d)
    p = p_ref[...].astype(jnp.float32)            # (TN, d)
    qp = jax.lax.dot_general(q, p, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    return jnp.maximum(qsq_ref[...] + psq_ref[...] - 2.0 * qp, 0.0)


def _merge_topk_tile(topd_ref, topg_ref, d2m, gidm, *, K: int, init):
    """Merge one tile's masked (dist, gid) pairs into the running sorted
    top-K accumulator blocks (shared by both kernels); returns the
    insertion passes it ran.

    gids are unique across the tile and the accumulator (stored rows are
    unique and the running K came from earlier, disjoint tiles); empty
    slots are the (F32_MAX, IMAX) sentinel, the lex-largest pair, so
    fewer-than-K hits pad the tail with sentinels.  A tile pair can only
    enter the top K if it is lex-smaller than the row's running K-th
    pair, so the merge runs m = min(K, the most such pairs of any row)
    passes: none on a tile that improves no row.  Pass j inserts the
    row's j-th smallest improving pair at its rank in the sorted
    accumulator, shifting the lanes above it up by one; a row with fewer
    improving pairs inserts the sentinel, which changes nothing.  Lanes
    >= K keep what was pushed out and are sliced off by the wrapper.
    """
    @pl.when(init)
    def _init():
        topd_ref[...] = jnp.full(topd_ref.shape, F32_MAX, jnp.float32)
        topg_ref[...] = jnp.full(topg_ref.shape, IMAX, jnp.int32)

    acc_d, acc_g = topd_ref[...], topg_ref[...]
    TR, KP = acc_d.shape
    kd, kg = acc_d[:, K - 1:K], acc_g[:, K - 1:K]     # running K-th pair
    improves = (d2m < kd) | ((d2m == kd) & (gidm < kg))
    m = jnp.minimum(jnp.max(jnp.sum(improves.astype(jnp.int32), axis=1)), K)

    @pl.when(m > 0)
    def _merge():
        lane = jax.lax.broadcasted_iota(jnp.int32, (TR, KP), 1)

        def insert(_, carry):
            last_d, last_g, out_d, out_g = carry
            after = improves & ((d2m > last_d)
                                | ((d2m == last_d) & (gidm > last_g)))
            bd = jnp.min(jnp.where(after, d2m, F32_MAX), axis=1,
                         keepdims=True)                        # (TR, 1)
            bg = jnp.min(jnp.where(after & (d2m == bd), gidm, IMAX),
                         axis=1, keepdims=True)                # lex tie-break
            below = (out_d < bd) | ((out_d == bd) & (out_g < bg))
            pos = jnp.sum(below.astype(jnp.int32), axis=1, keepdims=True)
            keep, put = lane < pos, lane == pos
            out_d = jnp.where(keep, out_d, jnp.where(
                put, bd, pltpu.roll(out_d, 1, 1)))
            out_g = jnp.where(keep, out_g, jnp.where(
                put, bg, pltpu.roll(out_g, 1, 1)))
            return bd, bg, out_d, out_g

        carry = (jnp.full((TR, 1), -1.0, jnp.float32),        # below any d2
                 jnp.zeros((TR, 1), jnp.int32), acc_d, acc_g)
        _, _, out_d, out_g = jax.lax.fori_loop(0, m, insert, carry)
        topd_ref[...] = out_d
        topg_ref[...] = out_g

    return m


def _count_and_merge(topd_ref, topg_ref, cnt_ref, passes_ref, d2, hit, gid,
                     *, K, init):
    """Shared epilogue: hit count + top-K merge of one tile, and the
    merge's insertion passes summed over the row tile's grid steps."""
    @pl.when(init)
    def _():
        cnt_ref[...] = jnp.zeros(cnt_ref.shape, jnp.int32)
        passes_ref[...] = jnp.zeros(passes_ref.shape, jnp.int32)
    cnt_ref[...] += jnp.sum(hit.astype(jnp.int32), axis=1, keepdims=True)
    passes_ref[...] += _merge_topk_tile(
        topd_ref, topg_ref, jnp.where(hit, d2, F32_MAX),
        jnp.where(hit, gid, IMAX), K=K, init=init)


def _bucket_search_kernel(q_ref, qsq_ref, qh_ref, ql_ref, probe_ref,
                          qtab_ref, p_ref, psq_ref, ph_ref, pl_ref, gid_ref,
                          pvalid_ref, ptab_ref, cr2_ref,
                          topd_ref, topg_ref, cnt_ref, passes_ref, *, L: int,
                          K: int):
    d2 = _sq_dist(q_ref, qsq_ref, p_ref, psq_ref)    # (TR, TN)

    # bucket match: OR over the L probed buckets of each query row
    qh, ql, probe = qh_ref[...], ql_ref[...], probe_ref[...]   # (TR, L)
    ph, plo = ph_ref[...], pl_ref[...]                         # (1, TN)
    match = jnp.zeros(d2.shape, jnp.bool_)
    for l in range(L):
        match = match | ((qh[:, l:l + 1] == ph) & (ql[:, l:l + 1] == plo)
                         & (probe[:, l:l + 1] > 0))
    # multi-table fusion: a stored row only answers probes of its own
    # table (rows of different tables live interleaved in one store)
    hit = (match & (pvalid_ref[...] > 0) & (qtab_ref[...] == ptab_ref[...])
           & (d2 <= cr2_ref[0, 0]))
    _count_and_merge(topd_ref, topg_ref, cnt_ref, passes_ref, d2, hit,
                     gid_ref[...], K=K, init=pl.program_id(1) == 0)


def vmem_bytes_per_step(d: int, L: int, K: int) -> int:
    """VMEM footprint of one grid step's blocks (inputs + accumulators),
    with every 2-D block padded to whole (8, 128) tiles.

    By construction this is independent of R and N -- the proof that the
    kernel never materialises the O(R*N) distance matrix: per step it
    holds one (TILE_R, TILE_N) distance tile plus O(TILE_R * K) outputs.
    """
    col = TILE_R * LANES * 4            # one (TILE_R, 1) column block
    row = 8 * TILE_N * 4                # one (1, TILE_N) row block
    probes = TILE_R * k_lanes(L) * 4    # one (TILE_R, L) word block
    in_bytes = (TILE_R * d * 4          # q tile
                + 2 * col               # qsq, qtable
                + 3 * probes            # bucket hi, bucket lo, probe
                + TILE_N * d * 4        # p tile
                + 6 * row)              # psq, hi, lo, gid, pvalid, ptable
    # topd, topg, cnt, passes
    out_bytes = TILE_R * k_lanes(K) * 4 * 2 + 2 * col
    dist_tile = TILE_R * TILE_N * 4               # d2 scratch residency
    return in_bytes + out_bytes + dist_tile


def gather_vmem_bytes_per_step(d: int, K: int) -> int:
    """VMEM per bucket-gather grid step: independent of N_shard AND of L
    (the probe expansion happens on the row axis, not in the block)."""
    col = TILE_R * LANES * 4
    row = 8 * TILE_N * 4
    in_bytes = (TILE_R * d * 4          # expanded q tile
                + 3 * col               # eqsq, span start, span end
                + TILE_N * d * 4        # gathered p tile
                + 3 * row)              # psq, gid, pvalid
    out_bytes = TILE_R * k_lanes(K) * 4 * 2 + 2 * col
    dist_tile = TILE_R * TILE_N * 4
    return in_bytes + out_bytes + dist_tile


def _col(x):
    """(R,) -> (R, 1) per-row column."""
    return x.reshape(-1, 1)


def _row(x):
    """(N,) -> (1, N) lane-dense per-point row."""
    return x.reshape(1, -1)


def _outputs(R: int, K: int):
    KP = k_lanes(K)
    return [jax.ShapeDtypeStruct((R, KP), jnp.float32),
            jax.ShapeDtypeStruct((R, KP), jnp.int32),
            jax.ShapeDtypeStruct((R, 1), jnp.int32),
            jax.ShapeDtypeStruct((R, 1), jnp.int32)]


def _passes(passes):
    """The merge's insertion passes over the whole grid, from the
    per-row-tile (R, 1) column (every row of a tile holds its tile's)."""
    return passes[::TILE_R, 0].sum(dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("L", "K", "interpret", "name",
                                             "n_tiles"))
def bucket_search_pallas(*, query: QueryBatch, store: StoreView, cr2,
                         L: int, K: int = 1, interpret: bool = False,
                         name: str = "bucket_search_pallas",
                         n_tiles: int | None = None):
    """Streaming masked top-K NN scan over EVERY stored row (full scan).

    Args (all keyword-only):
      query: QueryBatch with R rows -- q (R, d), qsq (R,), buckets
        (R, 2*L) int32 packed (hi, lo) per probed offset bucket, probe
        (R, L) int32 0/1, table (R,) int32.
      store: StoreView with N rows -- points (N, d), psq (N,), buckets
        (N, 2) int32, gid (N,), valid (N,) int32 0/1, table (N,).  The
        CSR fields are ignored here (this is the layout-agnostic path).
      cr2: scalar threshold (c*r)^2.
      K: neighbours to keep per row (static).
      name: the kernel's name in the compiled program and the device
        trace (static); each call site gives its own.
      n_tiles: store tiles to visit, rows [0, n_tiles * TILE_N) (static;
        default all N rows, N a tile multiple).  Only the grid stops
        there: the store columns are read in place, never sliced, and
        the rows past it must hold nothing valid.
    Returns:
      topd (R, K) f32 masked distance^2, ascending (F32_MAX sentinel pad),
      topg (R, K) int32 gids (IMAX sentinel pad),
      count (R,) int32 hits within cr,
      passes () int32 the merge's insertion passes over the whole grid.
    Rows are sorted by (distance^2, gid) lex order, so K=1 reproduces the
    old single-best contract exactly; a stored row only matches probes of
    its own table.
    """
    R, d = query.q.shape
    N = store.points.shape[0]
    if n_tiles is None:
        assert N % TILE_N == 0, N
        n_tiles = N // TILE_N
    assert R % TILE_R == 0 and 1 <= n_tiles * TILE_N <= N, (R, n_tiles, N)
    assert 1 <= K <= TILE_N, K
    KP = k_lanes(K)
    rows = lambda w: pl.BlockSpec((TILE_R, w), lambda i, j: (i, 0))
    pts = pl.BlockSpec((1, TILE_N), lambda i, j: (0, j))
    topd, topg, cnt, passes = pl.pallas_call(
        functools.partial(_bucket_search_kernel, L=L, K=K),
        grid=(R // TILE_R, n_tiles),
        in_specs=[
            rows(d), rows(1), rows(L), rows(L), rows(L), rows(1),
            pl.BlockSpec((TILE_N, d), lambda i, j: (j, 0)),
            pts, pts, pts, pts, pts, pts,
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[rows(KP), rows(KP), rows(1), rows(1)],
        out_shape=_outputs(R, K),
        compiler_params=_PARAMS,
        interpret=interpret,
        name=name,
    )(query.q, _col(query.qsq), query.buckets[:, 0::2],
      query.buckets[:, 1::2], query.probe, _col(query.table),
      store.points, _row(store.psq), _row(store.buckets[:, 0]),
      _row(store.buckets[:, 1]), _row(store.gid), _row(store.valid),
      _row(store.table), jnp.full((1, 1), cr2, jnp.float32))
    return topd[:, :K], topg[:, :K], cnt[:, 0], _passes(passes)


# ---------------------------------------------------------------------------
# CSR bucket gather: sorted-region scan that touches only each probe's
# own bucket row range
# ---------------------------------------------------------------------------

def _bucket_gather_kernel(base_ref, q_ref, qsq_ref, s_ref, e_ref,
                          p_ref, psq_ref, gid_ref, pvalid_ref, cr2_ref,
                          topd_ref, topg_ref, cnt_ref, passes_ref, *,
                          K: int):
    i, g = pl.program_id(0), pl.program_id(1)
    d2 = _sq_dist(q_ref, qsq_ref, p_ref, psq_ref)    # (TR, TN)

    # span mask: absolute store-row index of each column in this gathered
    # tile, against the expanded row's CSR span [start, end).  Rows in the
    # span share the probe's exact (table, bucket) triple by construction
    # of the sort + binary search, so no bucket/table compare is needed --
    # only liveness (tombstones stay in place until the next merge).
    col0 = (base_ref[i] + g) * TILE_N
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (1, TILE_N), 1)
    hit = ((cols >= s_ref[...]) & (cols < e_ref[...])
           & (pvalid_ref[...] > 0) & (d2 <= cr2_ref[0, 0]))
    _count_and_merge(topd_ref, topg_ref, cnt_ref, passes_ref, d2, hit,
                     gid_ref[...], K=K, init=g == 0)


@functools.partial(jax.jit, static_argnames=("K", "G", "interpret"))
def bucket_gather_pallas(base, q, qsq, start, end, p, psq, gid, pvalid,
                         cr2, *, K: int, G: int, interpret: bool = False):
    """CSR bucket-gather top-K scan over a bucket-sorted point region.

    One input row = one EXPANDED (query row, probe) pair, pre-sorted by
    span start so that the spans of a 128-row tile cluster into a small
    window of aligned point tiles.  ``base`` (E/TILE_R,) int32 is scalar-
    prefetched and steers the point-tile BlockSpec: grid step (i, g)
    streams aligned store tile ``base[i] + g``, so a row tile touches
    exactly G point tiles regardless of N.  The caller guarantees
    ``base[i] + G <= N // TILE_N`` and that every live span of tile i
    fits inside its window (checked outside; on overflow the caller runs
    the full scan instead -- correctness never depends on G).

    Args:
      base: (E // TILE_R,) int32 first store tile per row tile.
      q: (E, d) expanded query rows;  qsq: (E,) squared norms.
      start/end: (E,) int32 CSR span of each expanded probe (start == end
        for dead probes and padding rows).
      p/psq/gid/pvalid: the (N, ...) SORTED point region (padded rows
        must carry pvalid == 0).
      cr2: scalar threshold (c*r)^2.
      K: neighbours per expanded row (static);  G: window tiles (static).
    Returns (topd (E, K), topg (E, K), cnt (E,), passes ()) with the same
    sentinel, lex-order and pass-count contract as
    ``bucket_search_pallas``.
    """
    E, d = q.shape
    N = p.shape[0]
    assert E % TILE_R == 0 and N % TILE_N == 0, (E, N)
    assert 1 <= K <= TILE_N, K
    assert 1 <= G <= N // TILE_N, (G, N)
    KP = k_lanes(K)
    rows = lambda w: pl.BlockSpec((TILE_R, w), lambda i, g, b: (i, 0))
    pts = pl.BlockSpec((1, TILE_N), lambda i, g, b: (0, b[i] + g))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(E // TILE_R, G),
        in_specs=[
            rows(d), rows(1), rows(1), rows(1),
            pl.BlockSpec((TILE_N, d), lambda i, g, b: (b[i] + g, 0)),
            pts, pts, pts,
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[rows(KP), rows(KP), rows(1), rows(1)],
    )
    topd, topg, cnt, passes = pl.pallas_call(
        functools.partial(_bucket_gather_kernel, K=K),
        grid_spec=grid_spec,
        out_shape=_outputs(E, K),
        compiler_params=_PARAMS,
        interpret=interpret,
        name="bucket_gather_pallas",
    )(base, q, _col(qsq), _col(start), _col(end), p, _row(psq), _row(gid),
      _row(pvalid), jnp.full((1, 1), cr2, jnp.float32))
    return topd[:, :K], topg[:, :K], cnt[:, 0], _passes(passes)
