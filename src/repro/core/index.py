"""Distributed LSH index: the paper's Figure 3.1/3.2 on a JAX device mesh.

Machines = devices along one mesh axis ("shard").  The MapReduce shuffle /
Active-DHT send becomes a fixed-capacity ``jax.lax.all_to_all`` inside
``shard_map``.  The index hosts ``cfg.n_tables`` (T) independent hash
tables FUSED into one routed store -- every phase issues exactly ONE
cross-shard collective regardless of T (the paper's network-efficiency
argument applied to our own wire):

  insert: every data point p ships T rows (GH_t(p), <H_t(p), p, gid, t>)
          -- one per table -- through a single fused all_to_all ([x |
          packed | gid | table] packed into one int32 payload) and lands
          in free slots of the destination shard's append region
          (tombstoned slots are reused, occupancy is accounted)
  delete: gids are broadcast; owning shards tombstone all T copies and
          the bucket scan honours the mask
  query:  every query q ships f_q rows (GH_t(q+delta^t_i), <q, qid, t>)
          -- one per DISTINCT Key per table (Theorem 8 bounds the
          per-table count) -- again through ONE fused all_to_all
  search: the receiving shard regenerates the offsets from (qid, table)
          (consistent RNG) by GATHERING the row's own table's stacked
          parameters and hashing ONCE (O(L*k*d) per row, not O(T*L*k*d)),
          selects those whose Key == its own id, and scans its stored
          rows for bucket-equal SAME-TABLE points within distance cr
          (Fig 3.2 Reduce, with a table mask)
  return: each shard merges its local per-qid candidates across tables,
          then a single routed all_to_all ships every qid's local top-K
          (plus its emit count) ONLY to the qid's owner shard, which
          K-way merges the S contributions (dedup by gid).  This replaces
          the old all_gather + replicated merge: the receive volume drops
          from O(S*m*K) to O(m*K) per shard and the psum for emit counts
          rides inside the same collective.

``build`` is a thin wrapper: reset the store, then ``insert`` the whole
dataset.  The index is therefore a *streaming* service primitive -- the
store grows online under a mixed insert/delete/query workload and every
routed step reuses a cached compiled executable (keyed on batch shape and
store capacity) with donated store buffers, so steady-state serving does
no retracing and no store copies.

Static capacities are derived from the scheme's theoretical row bound
(LSHConfig.pairs_per_query, which sums over tables) times a slack factor;
overflow is counted and must be zero for a valid run (tests assert this).

With ``n_tables=1`` (and any K) the whole pipeline reproduces the
single-table index bit-for-bit: table 0 derives its parameters and
offsets from the same keys, rows route in the same order, and the return
merge applies the same (gid, dist) sort semantics.
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P
from jax.sharding import Mesh

from repro.core.config import LSHConfig, Scheme
from repro.core.hashing import (HashParams, StackedHashParams, hash_h,
                                pack_buckets, sample_stacked_params,
                                shard_key)
from repro.core.offsets import (query_offsets, query_offsets_by_table,
                                stacked_base_keys)
from repro.core import store_layout
from repro.core.spans import span
from repro.kernels import ops as kops
from repro.kernels.types import QueryBatch, StoreView

INF = jnp.float32(jnp.finfo(jnp.float32).max)
IMAX = jnp.int32(jnp.iinfo(jnp.int32).max)


# ---------------------------------------------------------------------------
# Dense dispatch: scatter rows into a (S*C, ...) send buffer by destination
# ---------------------------------------------------------------------------

def dispatch_slots(dest: jax.Array, valid: jax.Array, n_shards: int,
                   capacity: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Compute send-buffer slots for each row.

    Args:
      dest: (N,) int32 destination shard per row.
      valid: (N,) bool liveness per row.
    Returns:
      slot: (N,) int32 position in the (S*C,) buffer (= S*C for dropped),
      keep: (N,) bool rows that fit,
      drops: () int32 number of live rows beyond capacity.
    """
    N = dest.shape[0]
    big = jnp.where(valid, dest, n_shards)  # invalid rows sort last
    order = jnp.argsort(big)                # stable
    dsorted = big[order]
    starts = jnp.searchsorted(dsorted, jnp.arange(n_shards + 1))
    rank_sorted = jnp.arange(N) - starts[jnp.clip(dsorted, 0, n_shards)]
    rank = jnp.zeros((N,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    keep = valid & (rank < capacity)
    slot = jnp.where(keep, dest * capacity + rank, n_shards * capacity)
    drops = jnp.sum(valid & ~keep).astype(jnp.int32)
    return slot.astype(jnp.int32), keep, drops


def scatter_rows(slot: jax.Array, keep: jax.Array, rows: jax.Array,
                 n_slots: int, fill) -> jax.Array:
    """Scatter (N, ...) rows into a (n_slots, ...) buffer (drop overflow)."""
    buf = jnp.full((n_slots + 1,) + rows.shape[1:], fill, dtype=rows.dtype)
    buf = buf.at[slot].set(jnp.where(
        keep.reshape((-1,) + (1,) * (rows.ndim - 1)), rows,
        jnp.asarray(fill, rows.dtype)))
    return buf[:n_slots]


def first_occurrence_mask(keys: jax.Array, valid: jax.Array) -> jax.Array:
    """True on the FIRST live row of each key value, in index order.

    Sort-based (O(R log R) work, O(R) memory) -- replaces the old O(R^2)
    pairwise-equality matrix.  The stable sort preserves index order
    within equal keys, so ties resolve exactly like the pairwise
    formulation did.  Keys of invalid rows are ignored; the returned mask
    is False there.
    """
    R = keys.shape[0]
    big = jnp.where(valid, keys, jnp.iinfo(jnp.int32).max)
    order = jnp.argsort(big)
    s = big[order]
    first_sorted = jnp.concatenate(
        [jnp.ones((1,), bool), s[1:] != s[:-1]])
    first = jnp.zeros((R,), bool).at[order].set(first_sorted)
    return first & valid


def check_gid_range(gids: np.ndarray) -> None:
    """Reject gids outside [0, IMAX): the int32 sentinel IMAX marks
    empty/tombstoned slots and pads delete batches, so a caller-supplied
    gid >= IMAX (or negative, which int32 casts could wrap into) would
    silently alias padding and be ignored."""
    if gids.size and (int(gids.min()) < 0 or int(gids.max()) >= int(IMAX)):
        raise ValueError(
            f"gids must lie in [0, {int(IMAX)}): values >= the int32 "
            f"sentinel IMAX (or negative) alias empty-slot/batch padding")


def merge_topk(cand_d: jax.Array, cand_g: jax.Array,
               k: int) -> tuple[jax.Array, jax.Array]:
    """(rows, C) masked (dist, gid) candidates -> the k best per row with
    gid dedup: sort by (gid, dist), blank repeated gids, re-sort by
    (dist, gid).  Sentinel (INF, IMAX) pairs are fixed points, so rows
    with fewer than k real candidates pad with sentinels."""
    sg, sd = jax.lax.sort((cand_g, cand_d), dimension=1, num_keys=2)
    dup = jnp.concatenate(
        [jnp.zeros((sg.shape[0], 1), bool), sg[:, 1:] == sg[:, :-1]],
        axis=1)
    sd = jnp.where(dup, INF, sd)
    sg = jnp.where(dup, IMAX, sg)
    gd, gg = jax.lax.sort((sd, sg), dimension=1, num_keys=2)
    return gd[:, :k], gg[:, :k]


def _a2a(x: jax.Array, axis_name: str) -> jax.Array:
    """Tiled all_to_all over the leading (S*C) dimension."""
    return jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                              tiled=True)


def _f2i(x: jax.Array) -> jax.Array:
    """Bit-exact float32 -> int32 view (payload packing for fused a2a)."""
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _i2f(x: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x, jnp.float32)


def _keys_of(cfg: LSHConfig, p: HashParams, offs: jax.Array):
    """One table's offsets (L, d) -> (Key, packedH) per offset."""
    hk = hash_h(p, offs, cfg.W)                 # (L, k)
    packed = pack_buckets(p, hk)                # (L, 2)
    keyv = shard_key(p, cfg, hk)                # (L,)
    return keyv, packed


# ---------------------------------------------------------------------------
# Streaming store
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StoreState:
    """Per-shard routed append regions (leading dim = mesh shard axis).

    One region hosts the rows of ALL T tables, interleaved: each stored
    row carries the table it belongs to, and the bucket scan only matches
    probes of the same table.

    LSM-style two-region layout: slots ``[0, n_sorted)`` of every shard
    are the SORTED region -- rows in (table, packed hi, packed lo) lex
    order with per-row CSR spans in ``bucket_start``/``bucket_end``, the
    unused slots sentinel-filled (table = IMAX) so the query-side binary
    search stays valid at full region width.  Slots ``[n_sorted, cap)``
    are the unsorted insert TAIL, scanned by the full-scan kernel.
    Inserts only ever write tail slots (tombstoned sorted slots stay in
    place until the next merge), so the CSR columns are invariant under
    insert/delete; ``load_rows`` -- and through it ``compact()``, the
    auto-merge, snapshots and elastic restore -- emits a fully sorted
    store with an empty tail.  ``n_sorted == 0`` is the legacy unsorted
    layout (everything is tail).
    """
    x: jax.Array          # (S, cap, d) stored points
    packed: jax.Array     # (S, cap, 2) packed H buckets (uint32)
    gid: jax.Array        # (S, cap) global data ids (IMAX = empty)
    table: jax.Array      # (S, cap) int32 table id of each row
    key: jax.Array        # (S, cap) int32 routing Key (shard_key of the
    #                       row at insert time; shard-count-INDEPENDENT,
    #                       so compaction / elastic restore re-route rows
    #                       as Key mod S' without re-hashing)
    valid: jax.Array      # (S, cap) bool liveness (False = free/tombstone)
    bucket_start: jax.Array  # (S, cap) int32 CSR span start of the row's
    #                          own bucket inside the sorted region
    bucket_end: jax.Array    # (S, cap) int32 CSR span end (one past last)
    n_sorted: int = 0     # static region split: rows [0, n_sorted) sorted

    @property
    def capacity(self) -> int:
        return self.x.shape[1]


@dataclasses.dataclass
class BuildResult:
    store_x: jax.Array        # (S, cap, d) per-shard stored points
    store_packed: jax.Array   # (S, cap, 2) packed H buckets
    store_gid: jax.Array      # (S, cap) global data ids
    store_table: jax.Array    # (S, cap) table id per row
    store_key: jax.Array      # (S, cap) int32 routing Key per row
    store_valid: jax.Array    # (S, cap) bool
    data_load: np.ndarray     # (S,) live rows stored per shard (all tables)
    drops: int                # capacity overflow (must be 0)


@dataclasses.dataclass
class InsertResult:
    shard_load: np.ndarray    # (S,) live rows stored per shard after merge
    drops: int                # dispatch + append-region overflow (0 = clean)
    n_inserted: int           # points stored this call (table-0 copies)
    rows_stored: int          # routed rows stored (n_inserted * T if clean)
    capacity: int             # per-shard append-region capacity
    gid_start: Optional[int]  # minimum gid of this batch (None if empty)


@dataclasses.dataclass
class DeleteResult:
    n_deleted: int            # rows tombstoned across all shards/tables
    n_points: int             # distinct requested gids that had >= 1 live
    #                           row (the point-count mirror of n_deleted)
    shard_load: np.ndarray    # (S,) live rows remaining per shard


@dataclasses.dataclass
class CompactResult:
    capacity_before: int      # per-shard append-region rows before
    capacity_after: int       # per-shard append-region rows after
    n_live: int               # live rows rewritten (all tables)
    shard_load: np.ndarray    # (S,) live rows per shard (must be unchanged)


@dataclasses.dataclass
class QueryResult:
    topk_dist: np.ndarray     # (m, K) ascending sqrt distances within cr
    #                           (inf-padded past the available candidates)
    topk_gid: np.ndarray      # (m, K) matching global ids (IMAX-padded)
    n_within_cr: np.ndarray   # (m,) candidates emitted within cr (summed
    #                           over tables; a point stored in several
    #                           tables counts once per table it hit in)
    fq: np.ndarray            # (m,) rows shipped per query (Definition 7,
    #                           summed over tables, post-capacity-drop --
    #                           exactly what crossed the wire)
    query_load: np.ndarray    # (S,) live rows received per shard
    drops: int
    scan_counters: np.ndarray  # (S, len(kops.SCAN_COUNTERS)) what each
    #                            shard's scan saw (kernels.ops.bucket_search)

    @property
    def k_neighbors(self) -> int:
        return self.topk_dist.shape[1]

    @property
    def best_dist(self) -> np.ndarray:
        """(m,) nearest returned distance -- the old best-1 view.

        .. deprecated:: use ``topk_dist[:, 0]`` instead.
        """
        warnings.warn("QueryResult.best_dist is deprecated; use "
                      "topk_dist[:, 0]", DeprecationWarning, stacklevel=2)
        return self.topk_dist[:, 0]

    @property
    def best_gid(self) -> np.ndarray:
        """(m,) nearest returned gid -- the old best-1 view.

        .. deprecated:: use ``topk_gid[:, 0]`` instead.
        """
        warnings.warn("QueryResult.best_gid is deprecated; use "
                      "topk_gid[:, 0]", DeprecationWarning, stacklevel=2)
        return self.topk_gid[:, 0]


@dataclasses.dataclass
class DispatchedBatch:
    """Device-resident output of ``query_dispatch`` (stage 1 of 3).

    ``recv`` is the post-all_to_all routed payload -- each shard's
    (S*Cq, d+2) int32 block of [q | qid | table] rows, concatenated
    over shards.  It is consumed (donated) by ``query_scan``.
    """
    recv: jax.Array           # (S*S*Cq, d+2) routed int32 payload
    fq: jax.Array             # (m,) rows shipped per query
    drops: jax.Array          # (S,) capacity drops per source shard
    m: int
    Cq: int
    batch: Optional[int] = None   # the caller's batch number (spans' arg)


@dataclasses.dataclass
class ScannedBatch:
    """Device-resident output of ``query_scan`` (stage 2 of 3).

    ``ret`` holds each shard's local per-qid top-K (bitcast distances,
    gids, emit count): the routed return payload.  It is consumed
    (donated) by ``query_return``.  ``counters`` is what each shard's
    scan saw (``kernels.ops.SCAN_COUNTERS``), left on the device until
    the caller fetches the batch.
    """
    ret: jax.Array            # (S*m, 2K+1) int32 return payload
    recv_load: jax.Array      # (S,) live rows received per shard
    counters: jax.Array       # (S, len(kops.SCAN_COUNTERS)) int32
    m: int
    K: int
    batch: Optional[int] = None


def _host_query_result(gtopd, gtopg, gemit, fq, load, drops,
                       counters) -> QueryResult:
    """Fetch device query outputs into a host QueryResult (blocks)."""
    gtopd = np.asarray(gtopd)
    return QueryResult(
        topk_dist=np.sqrt(np.where(gtopd < np.float32(3e38), gtopd,
                                   np.inf)),
        topk_gid=np.asarray(gtopg),
        n_within_cr=np.asarray(gemit),
        fq=np.asarray(fq).reshape(-1),
        query_load=np.asarray(load),
        drops=int(np.asarray(drops).sum()),
        scan_counters=np.asarray(counters))


class DistributedLSHIndex:
    """T fused hash tables of the paper's scheme over one mesh axis.

    The paper punts on multi-table ("multiple hash tables can be
    obviously implemented in parallel"); implemented naively that costs T
    all_to_alls per insert and query plus T all_gathers on the return
    path.  Here all T tables share one routed store and one collective
    per phase: rows carry a table tag, the bucket scan masks across
    tables, and results union-merge per query.
    """

    def __init__(self, cfg: LSHConfig, mesh: Mesh, axis: str = "shard",
                 slack: float = 4.0, use_kernel: Optional[bool] = None,
                 k_neighbors: int = 1, merge_min_rows: int = 1024,
                 merge_frac: float = 0.25):
        """use_kernel=True routes the per-shard bucket search through the
        Pallas streaming kernels (kernels/bucket_search.py) instead of the
        jnp mask formulation -- identical results (tested), O(R*N) score
        matrix never materialised.  The default (None) takes the kernels
        on every accelerator and the jnp oracle on the CPU, where the
        kernels would only run in the Pallas interpreter; the oracle's
        dense (R, N) tiles do not fit at deployment store sizes.

        k_neighbors is the default K for ``query``: each query returns its
        K best (dist, gid) pairs within cr, union-merged across shards
        and tables.

        The store's layout alone picks the kernel path: the CSR bucket
        gather over a bucket-sorted region, the full scan over an
        unsorted store and when the gather window overflows (results
        are bitwise identical either way).  ``merge_min_rows`` /
        ``merge_frac`` set the LSM merge policy: after an insert, once
        the unsorted tail holds more than ``merge_min_rows`` live rows
        AND more than ``merge_frac`` of all live rows, the tail is
        folded into the sorted region (a ``compact()``-style
        rewrite)."""
        if mesh.shape[axis] != cfg.n_shards:
            raise ValueError(
                f"mesh axis {axis}={mesh.shape[axis]} != n_shards={cfg.n_shards}")
        self.cfg = cfg
        self.mesh = mesh
        self.axis = axis
        self._sharding = jax.sharding.NamedSharding(mesh, P(axis))
        self.slack = slack
        self.use_kernel = (jax.default_backend() != "cpu"
                           if use_kernel is None else use_kernel)
        self.merge_min_rows = merge_min_rows
        self.merge_frac = merge_frac
        if not 1 <= k_neighbors <= 128:
            raise ValueError(f"k_neighbors={k_neighbors} not in [1, 128]")
        self.k_neighbors = k_neighbors
        key = jax.random.PRNGKey(cfg.seed)
        kp, kq = jax.random.split(key)
        self._insert_fns: dict = {}
        self._delete_fns: dict = {}
        self._query_fns: dict = {}
        # CANONICAL form: all T tables' (A, b, alpha, beta, packing)
        # stacked on a leading T axis (sampled from split keys; table 0
        # == the single-table parameter stream, bit-for-bit), plus the
        # matching (T, ...) stack of offset base keys.  The per-table
        # ``table_params``/``table_keys`` below are deprecated views.
        self._stacked_params = sample_stacked_params(kp, cfg)
        self.params = self._stacked_params.table(0)
        self._stacked_keys = stacked_base_keys(kq, cfg.n_tables)
        self.base_key = kq
        self.store: Optional[StoreState] = None
        self._shard_load = np.zeros((cfg.n_shards,), np.int64)
        self._drops = 0
        self._n_live = 0
        self._next_gid = 0
        # store-layout accounting (host-side mirrors of the LSM state)
        self._sorted_live = 0     # live rows in the sorted region (sum S)
        self._tail_live = 0       # live rows in the unsorted tail (sum S)
        self._tail_hw = 0         # tail slots ever valid since the last
        #                           load_rows, one past the highest (max S)
        self._merges = 0          # tail merges performed (incl. compact)
        self._max_bucket = 0      # bucket-occupancy stats of the sorted
        self._mean_bucket = 0.0   # region (sizes the gather window)

    # ------------------------------------------------------------------
    # Hash-parameter surface: the stacked (T, ...) form is canonical.
    # Assignment is guarded (a populated store was bucketed/routed under
    # the OLD params -- probing it with new-param keys silently returns
    # garbage) and invalidates the cached compiled steps, which close
    # over the parameters.  The per-table ``table_params``/``table_keys``
    # list views are DEPRECATED compat shims.
    # ------------------------------------------------------------------
    @property
    def stacked_params(self) -> StackedHashParams:
        return self._stacked_params

    @stacked_params.setter
    def stacked_params(self, sparams: StackedHashParams) -> None:
        if sparams.n_tables != self.cfg.n_tables:
            raise ValueError(f"need {self.cfg.n_tables} tables, "
                             f"got {sparams.n_tables}")
        if self.store is not None:
            raise RuntimeError(
                "cannot replace table params on a populated index -- "
                "assign before build()/insert()")
        self._stacked_params = sparams
        self.params = sparams.table(0)
        self._insert_fns.clear()
        self._query_fns.clear()

    @property
    def stacked_keys(self) -> jax.Array:
        return self._stacked_keys

    @stacked_keys.setter
    def stacked_keys(self, keys: jax.Array) -> None:
        if keys.shape[0] != self.cfg.n_tables:
            raise ValueError(f"need {self.cfg.n_tables} keys, "
                             f"got {keys.shape[0]}")
        if self.store is not None:
            raise RuntimeError(
                "cannot replace offset keys on a populated index -- "
                "assign before build()/insert()")
        self._stacked_keys = keys
        self._query_fns.clear()

    @property
    def table_params(self) -> list[HashParams]:
        """.. deprecated:: use ``stacked_params`` (``.as_tables()`` /
        ``.table(t)`` for per-table views)."""
        warnings.warn(
            "DistributedLSHIndex.table_params is deprecated; use "
            "stacked_params.as_tables()", DeprecationWarning, stacklevel=2)
        return self.stacked_params.as_tables()

    @table_params.setter
    def table_params(self, tables) -> None:
        warnings.warn(
            "assigning DistributedLSHIndex.table_params is deprecated; "
            "assign stacked_params = StackedHashParams.stack(tables)",
            DeprecationWarning, stacklevel=2)
        self.stacked_params = StackedHashParams.stack(list(tables))

    @property
    def table_keys(self) -> list[jax.Array]:
        """.. deprecated:: use ``stacked_keys`` (a (T, 2) key stack)."""
        warnings.warn(
            "DistributedLSHIndex.table_keys is deprecated; use "
            "stacked_keys", DeprecationWarning, stacklevel=2)
        return [self._stacked_keys[t] for t in range(self.cfg.n_tables)]

    @table_keys.setter
    def table_keys(self, keys) -> None:
        warnings.warn(
            "assigning DistributedLSHIndex.table_keys is deprecated; "
            "assign stacked_keys = jnp.stack(keys)",
            DeprecationWarning, stacklevel=2)
        self.stacked_keys = jnp.stack(list(keys))

    # ------------------------------------------------------------------
    # Capacity policy
    # ------------------------------------------------------------------
    def _dispatch_capacity(self, n_rows: int) -> int:
        """Per-(source, dest) all_to_all block capacity for one insert.

        ``n_rows`` counts ROUTED rows per source shard (points x tables).
        Locality-preserving placement is skewed by design (Table 1).  Bulk
        builds concentrate around the balanced share, so the slack-sized
        block suffices; small streaming batches do not, so their share is
        doubled.  Either is clamped at n_rows: one source never sends more
        than all of its rows to one destination, so the clamp drops
        nothing (and on one shard it keeps the block at the batch size).
        """
        if self.cfg.data_capacity is not None:
            return self.cfg.data_capacity
        S = self.cfg.n_shards
        base = max(8, int(math.ceil(n_rows / S * self.slack)))
        if n_rows > 64 * S:           # bulk regime: slack-share sizing
            return min(n_rows, base)
        return min(n_rows, 2 * base)

    def _store_capacity(self, n_rows: int) -> int:
        """Per-shard append-region capacity for a target live ROW count
        (rows = points x n_tables)."""
        S = self.cfg.n_shards
        return max(8, int(math.ceil(n_rows / S * self.slack)))

    def _query_capacity(self, m_local: int) -> int:
        if self.cfg.query_capacity is not None:
            return self.cfg.query_capacity
        S = self.cfg.n_shards
        rows = m_local * self.cfg.pairs_per_query()   # summed over tables
        return max(8, int(math.ceil(rows / S * self.slack)))

    def _scan_rows(self, m: int, Cq: int) -> int:
        """Received rows the scan step searches: the S*Cq receive buffer
        compacted to its live rows, of which there are at most m*T (one
        per (qid, table) after the receive-side dedup)."""
        return min(self.cfg.n_shards * Cq, m * self.cfg.n_tables)

    def _gather_window(self, n_expanded: int) -> int:
        """Static CSR gather window (aligned store tiles per row tile).

        ``n_expanded`` is the receive buffer's S*Cq*L expanded probe
        rows, not the _scan_rows(m, Cq)*L the search gets after
        compaction.  The smaller count widens G into the range a row tile
        needs (``random1m`` in bench/: G = 2,687 against 2,661-3,729
        tiles), so the branch would flip with the queries, and there the
        CSR branch (gather + tail scan) took 233 ms a step against the
        fallback's 179 ms on one TPU v5e.

        A row tile holds TILE_R expanded probes sorted by span start; its
        window must cover their start spread (~ TILE_R * n_sorted /
        n_expanded rows when probes spread evenly over the region) plus
        the largest bucket.  Doubled for skew -- a too-small window only
        costs the traced full-scan fallback, never correctness.
        """
        st = self.store
        if st is None or st.n_sorted == 0:
            return kops.DEFAULT_WINDOW_TILES
        tr, tn = kops.TILE_R, kops.TILE_N
        n_tiles = -(-st.n_sorted // tn)
        spread = tr * st.n_sorted / max(n_expanded, 1)
        need = math.ceil(2.0 * (spread + self._max_bucket) / tn) + 2
        return int(min(n_tiles, max(2, need)))

    def _scan_extent(self) -> int:
        """The store's live extent, the static bound of every full scan:
        the sorted region plus the tail up to its high-water mark, no
        valid row lying past it.  The tail's part is rounded up to a
        power of two of whole store tiles, so a growing tail recompiles
        the scan step once per doubling, not once per insert."""
        st = self.store
        tiles = -(-self._tail_hw // kops.TILE_N)
        tail = kops.TILE_N << (tiles - 1).bit_length() if tiles else 0
        return min(st.capacity, st.n_sorted + tail)

    # ------------------------------------------------------------------
    # Store lifecycle
    # ------------------------------------------------------------------
    def init_store(self, capacity: int) -> StoreState:
        """Allocate empty per-shard append regions (capacity rows/shard).

        A fresh store is all tail: n_sorted = 0 until the first
        ``load_rows`` (compact / restore / merge) establishes the sorted
        region.
        """
        cfg = self.cfg
        S = cfg.n_shards
        cols = {"x": ((S, capacity, cfg.d), 0.0, jnp.float32),
                "packed": ((S, capacity, 2), 0, jnp.uint32),
                "gid": ((S, capacity), IMAX, jnp.int32),
                "table": ((S, capacity), 0, jnp.int32),
                "key": ((S, capacity), 0, jnp.int32),
                "valid": ((S, capacity), False, jnp.bool_),
                "bucket_start": ((S, capacity), 0, jnp.int32),
                "bucket_end": ((S, capacity), 0, jnp.int32)}
        # one program fills every column in place on each shard's device
        alloc = jax.jit(lambda: {c: jnp.full(*v) for c, v in cols.items()},
                        out_shardings=self._sharding)
        self.store = StoreState(**alloc(), n_sorted=0)
        self._shard_load = np.zeros((S,), np.int64)
        self._drops = 0
        self._n_live = 0
        self._sorted_live = 0
        self._tail_live = 0
        self._tail_hw = 0
        self._max_bucket = 0
        self._mean_bucket = 0.0
        return self.store

    def _grow_store(self, capacity: int) -> None:
        """Pad the append regions to a larger per-shard capacity.

        Growth only extends the tail, so the sorted region (a prefix of
        every shard) and its CSR columns are untouched.
        """
        st = self.store
        extra = capacity - st.capacity
        if extra <= 0:
            return
        def pad(a, fill):
            widths = [(0, 0)] * a.ndim
            widths[1] = (0, extra)
            return jnp.pad(a, widths, constant_values=fill)
        self.store = StoreState(
            x=pad(st.x, 0.0), packed=pad(st.packed, 0),
            gid=pad(st.gid, IMAX), table=pad(st.table, 0),
            key=pad(st.key, 0), valid=pad(st.valid, False),
            bucket_start=pad(st.bucket_start, 0),
            bucket_end=pad(st.bucket_end, 0),
            n_sorted=st.n_sorted)

    # ------------------------------------------------------------------
    # Insert: route T rows per point through ONE fused all_to_all into
    # free slots of the table-tagged append regions
    # ------------------------------------------------------------------
    def _make_insert_fn(self, n_loc: int, Ci: int, cap: int, ns: int):
        cfg = self.cfg
        sparams = self.stacked_params
        S, T, d = cfg.n_shards, cfg.n_tables, cfg.d
        axis = self.axis

        def insert_shard(x_loc, gid_loc, valid_loc, sx, sp, sg, stb, sk, sv):
            sx, sp = sx[0], sp[0]
            sg, stb, sk, sv = sg[0], stb[0], sk[0], sv[0]
            # ---- hashing: T routed copies per point in ONE vmapped pass
            # (params broadcast over the stacked T axis -- trace size is
            # independent of T), point-major row order (table t of point
            # i at row i*T+t) ----
            def hash_table(p):
                hk = hash_h(p, x_loc, cfg.W)               # (n_loc, k)
                return (pack_buckets(p, hk),
                        shard_key(p, cfg, hk).astype(jnp.int32))
            packs, keys = jax.vmap(hash_table)(sparams)    # (T, n_loc, .)
            packed = jnp.swapaxes(packs, 0, 1).reshape(n_loc * T, 2)
            rows_k = jnp.swapaxes(keys, 0, 1).reshape(n_loc * T)
            dest = jnp.mod(rows_k, S).astype(jnp.int32)
            rows_x = jnp.repeat(x_loc, T, axis=0)          # (n_loc*T, d)
            rows_g = jnp.repeat(gid_loc, T)
            rows_t = jnp.tile(jnp.arange(T, dtype=jnp.int32), n_loc)
            rows_v = jnp.repeat(valid_loc, T)
            slot, keep, d_drops = dispatch_slots(dest, rows_v, S, Ci)

            # ---- ONE fused all_to_all: [x | packed | gid | table | key]
            # as a single int32 payload (table < 0 marks empty slots; the
            # raw Key rides along so the stored row stays re-routable
            # under a different shard count without re-hashing) ----
            payload = jnp.concatenate([
                _f2i(rows_x),
                jax.lax.bitcast_convert_type(packed, jnp.int32),
                rows_g[:, None], rows_t[:, None],
                rows_k[:, None]], axis=1)
            nslots = S * Ci
            buf = scatter_rows(slot, keep, payload, nslots, -1)
            r = _a2a(buf, axis)                            # (S*Ci, d+5)
            rx = _i2f(r[:, :d])
            rp = jax.lax.bitcast_convert_type(r[:, d:d + 2], jnp.uint32)
            rg = r[:, d + 2]
            rt = r[:, d + 3]
            rk = r[:, d + 4]
            rv = rt >= 0

            # ---- append into free TAIL slots (tail tombstones are
            # reused; sorted-region slots -- live, tombstoned or sentinel
            # -- are off limits so the CSR layout stays invariant) ----
            blocked = sv | (jnp.arange(cap) < ns)
            n_free = jnp.sum(~blocked).astype(jnp.int32)
            free_order = jnp.argsort(blocked)              # free slots first,
            rank = jnp.cumsum(rv) - 1                      # in index order
            fit = rv & (rank < n_free)
            s_drops = jnp.sum(rv & ~fit).astype(jnp.int32)
            target = jnp.where(fit, free_order[jnp.clip(rank, 0, cap - 1)],
                               cap)                        # cap = sink row

            def merge(store, rows, fill):
                sink = jnp.full((1,) + store.shape[1:], fill, store.dtype)
                buf = jnp.concatenate([store, sink], axis=0)
                return buf.at[target].set(jnp.where(
                    fit.reshape((-1,) + (1,) * (rows.ndim - 1)), rows,
                    buf[target]))[:cap]

            nx = merge(sx, rx, 0.0)
            npk = merge(sp, rp, 0)
            ng = merge(sg, rg, IMAX)
            nt = merge(stb, rt, 0)
            nk = merge(sk, rk, 0)
            nv = merge(sv, fit, False)
            load = nv.sum().astype(jnp.int32)
            stored = fit.sum().astype(jnp.int32)
            stored_t0 = (fit & (rt == 0)).sum().astype(jnp.int32)
            # one past the highest valid tail slot, from n_sorted
            tail_hw = jnp.max(jnp.where(nv, jnp.arange(cap) - ns + 1, 0))
            return (nx[None], npk[None], ng[None], nt[None], nk[None],
                    nv[None], load[None], (d_drops + s_drops)[None],
                    stored[None], stored_t0[None],
                    tail_hw.astype(jnp.int32)[None])

        spec = P(axis)
        return jax.jit(shard_map(
            insert_shard, mesh=self.mesh,
            in_specs=(spec,) * 9, out_specs=(spec,) * 11,
            check_vma=False,   # pallas out_shape has no vma annotation
        ), donate_argnums=(3, 4, 5, 6, 7, 8))

    def insert(self, points: jax.Array,
               gids: Optional[jax.Array] = None) -> InsertResult:
        """Stream a batch of points into the routed store (T rows each).

        Any batch size is accepted: rows are padded to a multiple of
        n_shards with invalid rows (which ship nothing).  The store grows
        host-side when the live row count would exceed the slack-sized
        append regions, so a well-balanced stream never drops rows.

        The store buffers are DONATED to the compiled step (in-place
        update, no copy): on accelerators any previously captured
        ``build_result``/``store`` view is consumed by this call -- re-read
        ``self.build_result`` after every mutation instead of holding one.
        """
        cfg = self.cfg
        S, T = cfg.n_shards, cfg.n_tables
        n, d = points.shape
        if d != cfg.d:
            raise ValueError(f"points d={d} != cfg.d={cfg.d}")
        if gids is None:
            # the auto-gid counter must not mint the IMAX sentinel either
            # (reachable: an explicit insert at the legal boundary IMAX-1
            # advances _next_gid to IMAX)
            if n and self._next_gid + n - 1 >= int(IMAX):
                raise ValueError(
                    f"auto-gid space exhausted: this batch would assign "
                    f"gids up to {self._next_gid + n - 1} >= the int32 "
                    f"sentinel {int(IMAX)}; pass explicit in-range gids")
            gid_start = self._next_gid if n else None
            gids = np.arange(self._next_gid, self._next_gid + n,
                             dtype=np.int32)
            self._next_gid += n
        else:
            g64 = np.asarray(gids, np.int64)
            check_gid_range(g64)
            gids = g64.astype(np.int32)
            # the batch's actual minimum gid (NOT the unrelated _next_gid)
            gid_start = int(g64.min()) if n else None
            self._next_gid = max(self._next_gid, int(g64.max())
                                 + 1) if n else self._next_gid

        if self.store is None:
            self.init_store(self._store_capacity(n * T))
        else:
            # the sorted region's slots are unavailable to inserts, so a
            # sorted store sizes the TAIL for the incoming rows on top of
            # the fixed region width
            needed = self.store.n_sorted + self._store_capacity(
                self._tail_live + n * T) if self.store.n_sorted else \
                self._store_capacity(self._n_live + n * T)
            if needed > self.store.capacity:
                # geometric growth: capacity is part of the compiled-fn
                # cache key, so exact-fit growth would retrace every step
                self._grow_store(max(needed, 2 * self.store.capacity))
        st = self.store
        cap = st.capacity

        # pad on the host and place each shard's slice on its own device
        # (no whole-batch staging on the default device)
        n_pad = int(math.ceil(n / S)) * S if n else S
        x = np.zeros((n_pad, cfg.d), np.float32)
        x[:n] = np.asarray(points, np.float32)
        g = np.full((n_pad,), int(IMAX), np.int32)
        g[:n] = gids
        x, g, valid = jax.device_put((x, g, np.arange(n_pad) < n),
                                     self._sharding)
        n_loc = n_pad // S
        Ci = self._dispatch_capacity(n_loc * T)

        key = (n_loc, Ci, cap, st.n_sorted)
        fn = self._insert_fns.get(key)
        if fn is None:
            fn = self._insert_fns[key] = self._make_insert_fn(
                n_loc, Ci, cap, st.n_sorted)
        (nx, npk, ng, nt, nk, nv, load, drops, stored, stored_t0,
         tail_hw) = fn(x, g, valid, st.x, st.packed, st.gid, st.table,
                       st.key, st.valid)
        # inserts only touch tail slots: the CSR columns and the region
        # split carry over unchanged
        self.store = StoreState(x=nx, packed=npk, gid=ng, table=nt, key=nk,
                                valid=nv, bucket_start=st.bucket_start,
                                bucket_end=st.bucket_end,
                                n_sorted=st.n_sorted)
        n_drops = int(np.asarray(drops).sum())
        rows_stored = int(np.asarray(stored).sum())
        n_stored = int(np.asarray(stored_t0).sum())
        self._shard_load = np.asarray(load).astype(np.int64)
        self._drops += n_drops
        self._n_live += rows_stored
        self._tail_live += rows_stored
        # deletes never lower the mark: a tombstone stays inside it
        self._tail_hw = max(self._tail_hw, int(np.asarray(tail_hw).max()))
        result = InsertResult(shard_load=np.asarray(load), drops=n_drops,
                              n_inserted=n_stored, rows_stored=rows_stored,
                              capacity=cap, gid_start=gid_start)
        # LSM churn threshold: fold an eroding tail back into the sorted
        # region (only once a region exists -- a fresh bulk-built store
        # stays tail-only until the first compact()/snapshot establishes
        # one, preserving the legacy layout for pure-streaming flows)
        if (self.store.n_sorted > 0
                and self._tail_live > self.merge_min_rows
                and self._tail_live > self.merge_frac * max(self._n_live, 1)):
            self.merge_tail()
        return result

    # ------------------------------------------------------------------
    # Delete: tombstone rows by gid (honoured by the bucket scan; the
    # slots become free and are reused by later inserts).  All T table
    # copies of a gid are tombstoned.
    # ------------------------------------------------------------------
    def _make_delete_fn(self, n_del: int, cap: int, ns: int):
        axis = self.axis

        def delete_shard(gids_del, sv, sg):
            sv, sg = sv[0], sg[0]
            eq = sg[:, None] == gids_del[None, :]          # (cap, n_del)
            hit = jnp.any(eq, axis=1) & sv
            # per-requested-gid: did THIS shard hold a live row of it?
            # (ORed across shards on the host -> distinct-point count)
            hitg = jnp.any(eq & sv[:, None], axis=0)       # (n_del,)
            # region split of the tombstones (host tail accounting)
            hit_sorted = (hit & (jnp.arange(cap) < ns)).sum()
            nv = sv & ~hit
            return (nv[None], hit.sum().astype(jnp.int32)[None],
                    nv.sum().astype(jnp.int32)[None], hitg[None],
                    hit_sorted.astype(jnp.int32)[None])

        spec = P(axis)
        return jax.jit(shard_map(
            delete_shard, mesh=self.mesh,
            in_specs=(P(), spec, spec), out_specs=(spec,) * 5,
            check_vma=False,
        ), donate_argnums=(1,))

    def delete(self, gids) -> DeleteResult:
        """Tombstone the given global ids (missing ids are ignored).

        ``n_deleted`` counts tombstoned ROWS: deleting one point removes
        its copy from every table (n_tables rows when none were dropped).
        ``n_points`` counts the DISTINCT requested gids that had at least
        one live row (the point-level mirror of ``n_deleted``).
        """
        if self.store is None:
            raise RuntimeError("insert() or build() first")
        gids = np.asarray(gids, np.int64).reshape(-1)
        check_gid_range(gids)
        gids = gids.astype(np.int32)
        n_pad = max(8, int(math.ceil(len(gids) / 8)) * 8)
        padded = np.full((n_pad,), np.iinfo(np.int32).max, np.int32)
        padded[:len(gids)] = gids
        st = self.store
        key = (n_pad, st.capacity, st.n_sorted)
        fn = self._delete_fns.get(key)
        if fn is None:
            fn = self._delete_fns[key] = self._make_delete_fn(
                n_pad, st.capacity, st.n_sorted)
        nv, hits, load, hitg, hits_sorted = fn(
            jnp.asarray(padded), st.valid, st.gid)
        self.store = dataclasses.replace(st, valid=nv)
        n_deleted = int(np.asarray(hits).sum())
        anyhit = np.asarray(hitg).any(axis=0)[:len(gids)]
        n_points = len(np.unique(gids[anyhit]))
        self._shard_load = np.asarray(load).astype(np.int64)
        self._n_live -= n_deleted
        n_sorted_hits = int(np.asarray(hits_sorted).sum())
        self._sorted_live -= n_sorted_hits
        self._tail_live -= n_deleted - n_sorted_hits
        return DeleteResult(n_deleted=n_deleted, n_points=n_points,
                            shard_load=np.asarray(load))

    # ------------------------------------------------------------------
    # Build: thin wrapper -- fresh store + one bulk insert
    # ------------------------------------------------------------------
    def build(self, data: jax.Array,
              capacity: Optional[int] = None) -> BuildResult:
        """(Re)build the index from scratch: reset the store, route every
        data point's T table copies to their home shards and store them.

        Args:
          data: (n, d) global array; will be sharded over the mesh axis.
          capacity: optional per-shard append-region pre-reservation
            (ROWS -- points x n_tables) for a stream that will keep
            growing after the build.
        """
        n = data.shape[0]
        self._next_gid = 0
        self.init_store(max(capacity or 0,
                            self._store_capacity(n * self.cfg.n_tables)))
        self.insert(data)
        return self.build_result

    @property
    def build_result(self) -> Optional[BuildResult]:
        """Compatibility view of the streaming store."""
        if self.store is None:
            return None
        st = self.store
        return BuildResult(
            store_x=st.x, store_packed=st.packed, store_gid=st.gid,
            store_table=st.table, store_key=st.key, store_valid=st.valid,
            data_load=self._shard_load, drops=self._drops)

    @property
    def n_live(self) -> int:
        """Live stored rows (points x tables, minus deletions)."""
        return self._n_live

    @property
    def shard_load(self) -> np.ndarray:
        """Live stored rows per shard (the paper's load-balance metric)."""
        return np.asarray(self._shard_load)

    # ------------------------------------------------------------------
    # Live-rows-only serialise / re-route: the shared path behind
    # compact(), persist.snapshot and the elastic restore
    # ------------------------------------------------------------------
    def host_live_rows(self) -> dict:
        """Pull the LIVE rows of the store to host memory.

        Tombstoned and free slots are dropped, so any store rebuilt from
        this view is compacted by construction.  Returns a dict of flat
        ``(n_live, ...)`` numpy arrays: x, packed, gid, table, key.
        """
        cfg = self.cfg
        if self.store is None:
            return {"x": np.zeros((0, cfg.d), np.float32),
                    "packed": np.zeros((0, 2), np.uint32),
                    "gid": np.zeros((0,), np.int32),
                    "table": np.zeros((0,), np.int32),
                    "key": np.zeros((0,), np.int32)}
        st = self.store
        sel = np.flatnonzero(np.asarray(st.valid).reshape(-1))

        def flat(a):
            a = np.asarray(a)
            return a.reshape((-1,) + a.shape[2:])[sel]
        return {"x": flat(st.x), "packed": flat(st.packed),
                "gid": flat(st.gid), "table": flat(st.table),
                "key": flat(st.key)}

    def load_rows(self, rows: dict, capacity: Optional[int] = None
                  ) -> np.ndarray:
        """Install host rows into freshly re-routed, BUCKET-SORTED regions.

        Each row's destination is ``Key mod n_shards`` -- the stored Key
        is shard-count-independent, so the SAME call serves in-place
        compaction (destinations unchanged) and elastic restore onto a
        different shard count (rows redistribute without re-hashing).

        One host lexsort by (dest, table, packed hi, packed lo) both
        groups rows by shard and puts every shard's rows in CSR lex
        order, so the rebuilt store is fully sorted with an empty tail:
        the sorted region spans ``[0, n_sorted)`` on every shard
        (n_sorted = the fullest shard's row count; shorter shards pad
        with sentinel rows that sort last), per-row CSR spans come from
        one run-length pass, and ``capacity - n_sorted`` tail slots
        remain for streaming inserts.  Returns the per-shard live-row
        counts.
        """
        cfg = self.cfg
        S, d = cfg.n_shards, cfg.d
        key = np.asarray(rows["key"], np.int64)
        table = np.asarray(rows["table"], np.int64)
        packed = np.asarray(rows["packed"], np.uint32).reshape(-1, 2)
        n = int(key.shape[0])
        dest = np.mod(key, S)
        counts = np.bincount(dest, minlength=S).astype(np.int64)
        cap_sorted = int(counts.max(initial=0))
        cap = max(8, cap_sorted + 8, self._store_capacity(n),
                  int(capacity or 0))
        order = np.lexsort((packed[:, 1], packed[:, 0], table, dest))
        sdest = dest[order]
        slot = (np.arange(n) - np.searchsorted(sdest, sdest)).astype(
            np.int64)

        def place(vals, shape, dtype, fill):
            buf = np.full((S, cap) + shape, fill, dtype)
            buf[sdest, slot] = np.asarray(vals, dtype)[order]
            return buf
        hx = place(rows["x"], (d,), np.float32, 0.0)
        hp = place(rows["packed"], (2,), np.uint32,
                   store_layout.SENTINEL_PACKED)
        hg = place(rows["gid"], (), np.int32, int(IMAX))
        ht = place(rows["table"], (), np.int32, int(IMAX))
        hk = place(rows["key"], (), np.int32, 0)
        hv = np.zeros((S, cap), bool)
        hv[sdest, slot] = True
        # sentinel rows live only inside the sorted region; the tail
        # keeps the legacy zero fill (it is scanned, not searched)
        hp[:, cap_sorted:] = 0
        ht[:, cap_sorted:] = 0

        # per-shard slot-relative CSR spans (rows of one shard are
        # contiguous in the lexsorted order, already in CSR lex order)
        hbs = np.zeros((S, cap), np.int32)
        hbe = np.zeros((S, cap), np.int32)
        max_b, sum_b = 0, 0
        for s in range(S):
            c = int(counts[s])
            if c == 0:
                continue
            bs, be = store_layout.bucket_spans(ht[s, :c], hp[s, :c])
            hbs[s, :c], hbe[s, :c] = bs, be
            mx, mn = store_layout.bucket_stats(bs, be, c)
            max_b = max(max_b, mx)
            sum_b += int(round(mn * c))
        self._max_bucket = max_b
        self._mean_bucket = sum_b / n if n else 0.0

        # host numpy -> per-shard slices straight to their devices
        put = lambda a: jax.device_put(a, self._sharding)
        self.store = StoreState(x=put(hx), packed=put(hp), gid=put(hg),
                                table=put(ht), key=put(hk), valid=put(hv),
                                bucket_start=put(hbs), bucket_end=put(hbe),
                                n_sorted=cap_sorted)
        self._shard_load = counts
        self._n_live = n
        self._sorted_live = n
        self._tail_live = 0
        self._tail_hw = 0
        return counts

    def compact(self) -> CompactResult:
        """Rewrite the append regions live-rows-only (tombstones dropped).

        Rows keep their shard (Key mod S is unchanged), so ``shard_load``
        is preserved exactly and query results are bit-identical (the
        top-K merge and emit counts are slot-order-independent); the
        per-shard capacity shrinks back to the slack policy for the
        current live-row count.
        """
        if self.store is None:
            raise RuntimeError("insert() or build() first")
        before = self.store.capacity
        with span("lsh.merge", n_live=self._n_live):
            load = self.load_rows(self.host_live_rows())
        self._merges += 1
        return CompactResult(capacity_before=before,
                             capacity_after=self.store.capacity,
                             n_live=self._n_live, shard_load=load)

    def merge_tail(self) -> CompactResult:
        """Fold the unsorted insert tail into the sorted region (the LSM
        merge step).  Identical to ``compact()`` -- a live-rows-only
        rewrite through ``load_rows`` always emits a fully sorted store
        -- but named for the auto-merge call site so profiles and logs
        show merges as merges."""
        return self.compact()

    @property
    def layout(self) -> dict:
        """Store-layout health: region sizes and merge count (the
        numbers ``ServiceStats.summary`` surfaces for operators)."""
        st = self.store
        return {
            "n_sorted": 0 if st is None else st.n_sorted,
            "sorted_rows": self._sorted_live,
            "tail_rows": self._tail_live,
            "tail_hw": self._tail_hw,
            "merges": self._merges,
            "max_bucket": self._max_bucket,
            "mean_bucket": self._mean_bucket,
        }

    # ------------------------------------------------------------------
    # Query: one routed step in three compiled stages (dispatch / scan /
    # return), cut at its two all_to_all boundaries.  Stage payloads are
    # exact int32 buffers (floats bitcast), so nothing is lost crossing a
    # boundary.  Each stage call only ENQUEUES device work (jax dispatch
    # is async), so a pipeline can issue batch i+1's dispatch before
    # batch i's scan and return have executed -- the host blocks only
    # when it fetches a retired batch's results.  ``query()`` runs the
    # same three compiled programs back to back.
    # ------------------------------------------------------------------
    def _make_query_dispatch_fn(self, m: int, Cq: int, donate: bool):
        cfg = self.cfg
        sparams, skeys = self.stacked_params, self.stacked_keys
        S, L, T = cfg.n_shards, cfg.L, cfg.n_tables
        axis = self.axis
        m_loc = m // S

        def live_mask(keyv, packed):
            if cfg.scheme == Scheme.SIMPLE:
                eq = jnp.all(packed[:, None, :] == packed[None, :, :], -1)
            else:
                eq = keyv[:, None] == keyv[None, :]
            earlier = jnp.arange(L)[:, None] > jnp.arange(L)[None, :]
            return ~jnp.any(eq & earlier, axis=-1)      # (L,)

        @jax.named_scope("lsh.dispatch")
        def dispatch_body(q_loc, qid_loc):
            """Stage 1: route.  Hash T x L offsets, pack the payload and
            issue the ONE fused dispatch all_to_all."""
            # ---- route: each local query's T x L offsets hashed in ONE
            # vmapped pass, params broadcast over the stacked T axis (the
            # trace no longer grows with T) ----
            def route_table(p, bk):
                offs = jax.vmap(
                    lambda i, q: query_offsets(bk, i, q, L, cfg.r))(
                        qid_loc, q_loc)                  # (m_loc, L, d)
                keyv, packed = jax.vmap(lambda o: _keys_of(cfg, p, o))(offs)
                return keyv, jax.vmap(live_mask)(keyv, packed)
            key_t, live_t = jax.vmap(route_table)(sparams, skeys)
            keyv = jnp.swapaxes(key_t, 0, 1)             # (m_loc, T, L)
            live = jnp.swapaxes(live_t, 0, 1)
            dest = jnp.mod(keyv, S).astype(jnp.int32).reshape(-1)
            rows_q = jnp.repeat(q_loc, T * L, axis=0)    # (m_loc*T*L, d)
            rows_id = jnp.repeat(qid_loc, T * L)
            rows_t = jnp.tile(
                jnp.repeat(jnp.arange(T, dtype=jnp.int32), L), m_loc)
            slot, keep, drops = dispatch_slots(
                dest, live.reshape(-1), S, Cq)
            # Definition 7 on the wire: bill only rows that actually
            # shipped (capacity-dropped rows cost nothing)
            fq_local = keep.reshape(m_loc, T * L).sum(axis=1).astype(
                jnp.int32)

            # ---- ONE fused all_to_all: [q | qid | table] as int32 ----
            payload = jnp.concatenate([
                _f2i(rows_q), rows_id[:, None], rows_t[:, None]], axis=1)
            nslots = S * Cq
            sbuf = scatter_rows(slot, keep, payload, nslots, IMAX)
            r = _a2a(sbuf, axis)                         # (S*Cq, d+2)
            return r, fq_local, drops

        def dispatch_shard(q_loc, qid_loc):
            r, fq_local, drops = dispatch_body(q_loc, qid_loc)
            return r, fq_local, drops[None]

        spec = P(self.axis)
        return jax.jit(shard_map(
            dispatch_shard, mesh=self.mesh,
            in_specs=(spec, spec), out_specs=(spec,) * 3,
            check_vma=False,   # pallas out_shape has no vma annotation
        ), donate_argnums=(0,) if donate else ())

    def _make_query_scan_fn(self, m: int, Cq: int, K: int, ns: int, G: int,
                            extent: int):
        """``ns`` (the store's ``n_sorted``) picks the bucket search's
        path; ``G`` sizes the gather window and ``extent``
        (``_scan_extent``) bounds the kernels' full scans."""
        cfg = self.cfg
        sparams, skeys = self.stacked_params, self.stacked_keys
        S, L, T, d = cfg.n_shards, cfg.L, cfg.n_tables, cfg.d
        axis = self.axis
        Rc = self._scan_rows(m, Cq)         # received rows the scan searches
        use_kernel = self.use_kernel

        @jax.named_scope("lsh.scan")
        def scan_body(r, store_x, store_packed, store_gid, store_table,
                      store_valid, store_bs, store_be):
            """Stage 2: receive-side hash-once + bucket search + local
            per-qid union across tables.  No collectives.  Also returns
            what the bucket search saw (``kernels.ops.SCAN_COUNTERS``).

            Only the first row per (qid, table) probes anything, so the
            S*Cq received rows are compacted, in order, to the Rc =
            min(S*Cq, m*T) rows that can be live before the search.  The
            bound is exact: the dedup leaves one row per key rid*T + rtab
            in [0, m*T), and a row's answers do not depend on the other
            rows of its call, so no answer changes."""
            me = jax.lax.axis_index(axis)
            rq = _i2f(r[:, :d])
            rid = r[:, d]
            rtab = r[:, d + 1]
            rvalid = rid != IMAX
            recv_load = rvalid.sum().astype(jnp.int32)

            # Two rows of one (query, table) can land on the same shard
            # when two distinct Keys collide mod S (always possible for
            # SIMPLE, rare otherwise).  Each row probes ALL buckets its
            # table owns on this shard, so keep only the first row per
            # (qid, table) -- sort-based, no R x R matrix.
            rvalid = first_occurrence_mask(
                jnp.where(rvalid, rid * T + rtab, IMAX), rvalid)
            live = jnp.nonzero(rvalid, size=Rc, fill_value=0)[0]
            rq, rid, rtab = rq[live], rid[live], rtab[live]
            rvalid = jnp.arange(Rc) < rvalid.sum()
            rid_safe = jnp.where(rvalid, rid, 0)
            rtab_safe = jnp.where(rvalid, rtab, 0)

            # ---- regenerate offsets & select buckets owned by me: gather
            # each row's OWN table params / offset key and hash ONCE
            # (O(L*k*d) per row instead of the old hash-under-all-T-and-
            # where-select, which paid O(T*L*k*d)) ----
            roffs = query_offsets_by_table(
                skeys, rtab_safe, rid_safe, rq, L, cfg.r)  # (R, L, d)
            rkey, rpacked = jax.vmap(lambda p, o: _keys_of(cfg, p, o))(
                sparams.gather(rtab_safe), roffs)          # (R, L) (R, L, 2)
            mine = (jnp.mod(rkey, S) == me) & rvalid[:, None]  # (R, L)
            # first-occurrence dedupe of H-buckets within the selected set
            eqp = jnp.all(rpacked[:, :, None, :] == rpacked[:, None, :, :], -1)
            earlier = jnp.arange(L)[:, None] > jnp.arange(L)[None, :]
            firstocc = ~jnp.any(eqp & earlier[None], axis=-1)
            probe = mine & firstocc                            # (R, L)

            # ---- bucket search (Fig 3.2 Reduce body), local top-K,
            # stored rows only answer probes of their own table.  The
            # store's layout picks the path: the Pallas CSR gather over a
            # sorted region (ns > 0), else the Pallas full scan; the jnp
            # oracle (use_kernel=False) is always a full scan -- it is
            # the XLA lowering for sharded dry runs ----
            qbatch = QueryBatch(
                q=rq, qsq=jnp.sum(rq ** 2, -1),
                buckets=jax.lax.bitcast_convert_type(
                    rpacked, jnp.int32).reshape(rpacked.shape[0], -1),
                probe=probe.astype(jnp.int32), table=rtab_safe)
            sview = StoreView(
                points=store_x, psq=jnp.sum(store_x ** 2, -1),
                buckets=jax.lax.bitcast_convert_type(
                    store_packed, jnp.int32),
                gid=store_gid, valid=store_valid.astype(jnp.int32),
                table=store_table, bucket_start=store_bs,
                bucket_end=store_be, n_sorted=ns, extent=extent)
            row_d, row_g, row_emit, counters = kops.bucket_search(
                query=qbatch, store=sview,
                cr2=float(np.float32((cfg.c * cfg.r) ** 2)), L=L, k=K,
                use_kernel=use_kernel, window_tiles=G)

            # ---- local union across tables: this shard holds at most
            # one live row per (qid, table), so scatter per-row top-Ks
            # into (qid, table) slots and K-way merge the T tables
            # (dedup by gid: a point stored in several tables counts
            # once) ----
            with jax.named_scope("merge"):
                idx = jnp.where(rvalid, rid * T + rtab, m * T)  # sink m*T
                loc_d = jnp.full((m * T + 1, K), INF).at[idx].set(
                    jnp.where(rvalid[:, None], row_d, INF))
                loc_g = jnp.full((m * T + 1, K), IMAX, jnp.int32).at[
                    idx].set(jnp.where(rvalid[:, None], row_g, IMAX))
                loc_d, loc_g = merge_topk(
                    loc_d[:m * T].reshape(m, T * K),
                    loc_g[:m * T].reshape(m, T * K), K)         # (m, K)
                qid_sink = jnp.where(rvalid, rid, m)
                emit = jnp.zeros((m + 1,), jnp.int32).at[qid_sink].add(
                    jnp.where(rvalid, row_emit, 0))[:m]

            # ---- return payload: each qid's local top-K (+ emit count)
            # as one int32 row, ready for the routed return a2a ----
            ret = jnp.concatenate([
                _f2i(loc_d), loc_g, emit[:, None]], axis=1)  # (m, 2K+1)
            return ret, recv_load, counters

        def scan_shard(r, store_x, store_packed, store_gid, store_table,
                       store_valid, store_bs, store_be):
            # stores arrive with a leading per-shard block dim of 1
            ret, recv_load, counters = scan_body(
                r, store_x[0], store_packed[0], store_gid[0],
                store_table[0], store_valid[0], store_bs[0], store_be[0])
            return ret, recv_load[None], counters[None]

        spec = P(self.axis)
        return jax.jit(shard_map(
            scan_shard, mesh=self.mesh,
            in_specs=(spec,) * 8, out_specs=(spec,) * 3,
            check_vma=False,
        ), donate_argnums=(0,))   # the routed recv buffer dies here

    def _make_query_return_fn(self, m: int, K: int):
        S = self.cfg.n_shards
        axis = self.axis
        m_loc = m // S

        @jax.named_scope("lsh.return")
        def return_body(ret):
            """Stage 3: ONE routed all_to_all ships each qid's local
            top-K (+ emit count) only to the qid's OWNER shard
            (qid // m_loc), replacing the old all_gather + replicated
            K-way merge + emit psum: O(m*K) received per shard instead
            of O(S*m*K)."""
            recv = _a2a(ret, axis).reshape(S, m_loc, 2 * K + 1)
            cand_d = jnp.moveaxis(_i2f(recv[:, :, :K]), 0, 1)
            cand_g = jnp.moveaxis(recv[:, :, K:2 * K], 0, 1)
            gtopd, gtopg = merge_topk(
                cand_d.reshape(m_loc, S * K),
                cand_g.reshape(m_loc, S * K), K)            # (m_loc, K)
            gemit = recv[:, :, 2 * K].sum(axis=0).astype(jnp.int32)
            return gtopd, gtopg, gemit

        def return_shard(ret):
            return return_body(ret)

        spec = P(self.axis)
        return jax.jit(shard_map(
            return_shard, mesh=self.mesh,
            in_specs=(spec,), out_specs=(spec,) * 3,
            check_vma=False,
        ), donate_argnums=(0,))   # the return payload dies here

    def _check_query_batch(self, m: int, k_neighbors: Optional[int]) -> int:
        """Refuse a batch of ``m`` queries the query steps cannot take;
        return its K."""
        if self.store is None:
            raise RuntimeError("call build() or insert() first")
        S = self.cfg.n_shards
        if m % S:
            raise ValueError(f"m={m} must divide by n_shards={S}")
        K = self.k_neighbors if k_neighbors is None else k_neighbors
        if not 1 <= K <= 128:
            raise ValueError(f"k_neighbors={K} not in [1, 128]")
        return K

    def _stage_fn(self, key: tuple, make):
        """The compiled stage program cached under ``key``."""
        fn = self._query_fns.get(key)
        if fn is None:
            fn = self._query_fns[key] = make()
        return fn

    def _dispatch(self, queries: jax.Array, donate: bool,
                  batch: Optional[int]) -> DispatchedBatch:
        m = queries.shape[0]
        Cq = self._query_capacity(m // self.cfg.n_shards)
        fn = self._stage_fn(
            ("dispatch", m, Cq, donate),
            lambda: self._make_query_dispatch_fn(m, Cq, donate))
        recv, fq, drops = fn(queries, jnp.arange(m, dtype=jnp.int32))
        return DispatchedBatch(recv=recv, fq=fq, drops=drops, m=m, Cq=Cq,
                               batch=batch)

    def _scan(self, disp: DispatchedBatch, K: int) -> ScannedBatch:
        st, m, Cq = self.store, disp.m, disp.Cq
        G = self._gather_window(self.cfg.n_shards * Cq * self.cfg.L)
        ext = self._scan_extent()
        fn = self._stage_fn(
            ("scan", m, st.capacity, Cq, K, st.n_sorted, G, ext),
            lambda: self._make_query_scan_fn(m, Cq, K, st.n_sorted, G, ext))
        ret, recv_load, counters = fn(
            disp.recv, st.x, st.packed, st.gid, st.table, st.valid,
            st.bucket_start, st.bucket_end)
        return ScannedBatch(ret=ret, recv_load=recv_load, counters=counters,
                            m=m, K=K, batch=disp.batch)

    def _return(self, scanned: ScannedBatch
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
        m, K = scanned.m, scanned.K
        fn = self._stage_fn(("return", m, K),
                            lambda: self._make_query_return_fn(m, K))
        return fn(scanned.ret)

    def query(self, queries: jax.Array, donate: bool = False,
              k_neighbors: Optional[int] = None,
              batch: Optional[int] = None) -> QueryResult:
        """Answer a batch of queries (m, d), m divisible by n_shards.

        Runs the three compiled stages of ``query_dispatch`` /
        ``query_scan`` / ``query_return`` back to back, under the one
        ``lsh.query`` span, and fetches the result (blocks).

        donate=True donates the query buffer to the compiled dispatch
        (serving front-ends stage queries into a scratch buffer that is
        dead after the call -- avoids one device copy per flush).

        k_neighbors overrides the index-level default K for this call
        (each distinct K compiles its own scan and return, cached).

        batch: the caller's number for this batch, the ``lsh.query``
        span's arg (``core/spans.py``).
        """
        with span("lsh.query", batch=batch):
            K = self._check_query_batch(queries.shape[0], k_neighbors)
            disp = self._dispatch(queries, donate, batch)
            scanned = self._scan(disp, K)
            # each shard returned exactly its own qids' results (the routed
            # return path); the sharded outputs concatenate to (m, K)
            return _host_query_result(
                *self._return(scanned), disp.fq, scanned.recv_load,
                disp.drops, scanned.counters)

    def query_dispatch(self, queries: jax.Array, donate: bool = False,
                       batch: Optional[int] = None) -> DispatchedBatch:
        """Stage 1/3: hash + route the batch through the dispatch a2a.

        Returns device-resident handles immediately (async dispatch).
        donate=True donates the query staging buffer -- the pipeline
        must not refill that buffer until this batch retires.  ``batch``
        (the caller's number for it) travels with the batch as the arg
        of its ``lsh.dispatch`` / ``lsh.scan`` / ``lsh.return`` spans.
        """
        with span("lsh.dispatch", batch=batch):
            self._check_query_batch(queries.shape[0], None)
            return self._dispatch(queries, donate, batch)

    def query_scan(self, disp: DispatchedBatch,
                   k_neighbors: Optional[int] = None) -> ScannedBatch:
        """Stage 2/3: per-shard bucket search over the routed payload.

        Consumes (donates) ``disp.recv``; no collectives are issued.
        """
        K = self._check_query_batch(disp.m, k_neighbors)
        with span("lsh.scan", batch=disp.batch):
            return self._scan(disp, K)

    def query_return(self, scanned: ScannedBatch
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Stage 3/3: routed return a2a + owner-shard K-way merge.

        Consumes (donates) ``scanned.ret``; returns device-resident
        (topk_dist^2, topk_gid, n_within_cr) -- fetch with np.asarray
        to block on the batch.
        """
        with span("lsh.return", batch=scanned.batch):
            return self._return(scanned)
