"""Analytic cluster simulator: exact traffic / load-balance / recall numbers
for any shard count WITHOUT building a device mesh.

This computes the same quantities the distributed `index.py` path produces
(cross-checked in tests at small shard counts), but vectorised over the
whole dataset, so benchmarks can reproduce the paper's 1024-reducer Table 1
and the Fig 4.1 shuffle-size curves quickly on one host.

Multi-table (``cfg.n_tables`` = T > 1) accounting mirrors the fused index:
each table hashes with its own split-key parameters, rows/loads sum over
tables (with a per-table breakdown in the report), and recall is computed
on the UNION candidate set -- a point is a candidate iff ANY table
co-buckets it with any probed offset of that table.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import accounting
from repro.core.config import LSHConfig, Scheme
from repro.core.hashing import (HashParams, StackedHashParams, hash_h,
                                pack_buckets, sample_stacked_params,
                                shard_key, shard_of)
from repro.core.offsets import batch_query_offsets, stacked_base_keys


def _dedupe_mask_2d(vals: jax.Array) -> jax.Array:
    """(m, L) int32 -> bool mask marking the FIRST occurrence of each value
    within each row (the paper's 'for each unique value x in the set')."""
    dup = (vals[:, :, None] == vals[:, None, :])  # (m, L, L)
    idx = jnp.arange(vals.shape[1])
    earlier = idx[None, :, None] > idx[None, None, :]  # j earlier than i
    seen_before = jnp.any(dup & earlier, axis=-1)
    return ~seen_before


def _dedupe_mask_packed(packed: jax.Array) -> jax.Array:
    """(m, L, 2) packed buckets -> first-occurrence mask (m, L)."""
    eq = jnp.all(packed[:, :, None, :] == packed[:, None, :, :], axis=-1)
    idx = jnp.arange(packed.shape[1])
    earlier = idx[None, :, None] > idx[None, None, :]
    return ~jnp.any(eq & earlier, axis=-1)


@dataclasses.dataclass
class SimState:
    """Sampled scheme state.  The stacked leading-T-axis form is the ONLY
    stored representation (same canonical derivation as
    ``DistributedLSHIndex``); per-table params/keys are derived views."""
    cfg: LSHConfig
    stacked_params: StackedHashParams  # CANONICAL: leading-T-axis params
    stacked_keys: jax.Array            # (T, ...) offset base keys

    @property
    def params(self) -> HashParams:
        """Table 0 (single-table compat view)."""
        return self.stacked_params.table(0)

    @property
    def base_key(self) -> jax.Array:
        """Table 0 offset key (== the pre-split base key)."""
        return self.stacked_keys[0]

    @property
    def table_params(self) -> List[HashParams]:
        return self.stacked_params.as_tables()

    @property
    def table_keys(self) -> List[jax.Array]:
        return [self.stacked_keys[t] for t in range(self.cfg.n_tables)]


def make_sim(cfg: LSHConfig) -> SimState:
    key = jax.random.PRNGKey(cfg.seed)
    kp, kq = jax.random.split(key)
    return SimState(cfg, sample_stacked_params(kp, cfg),
                    stacked_base_keys(kq, cfg.n_tables))


def _data_shards(sim: SimState, data: jax.Array) -> np.ndarray:
    """(T, n) destination shard of every point under every table -- one
    vmapped hash pass over the stacked T axis (matches the fused index's
    insert dispatch)."""
    cfg = sim.cfg
    return np.asarray(jax.vmap(
        lambda p: shard_of(p, cfg, hash_h(p, data, cfg.W)))(
            sim.stacked_params))


def _probe_hashes(sim: SimState, queries: jax.Array, qids: jax.Array,
                  table: int = 0) -> tuple[jax.Array, jax.Array]:
    """First-layer bucket vectors of every probe of one table: (m, L', k)
    int32 plus a (m, L') validity mask (False on mplsh sentinel rows)."""
    cfg = sim.cfg
    params = sim.table_params[table]
    base_key = sim.table_keys[table]
    if cfg.probes == "mplsh":
        from repro.core.multiprobe import batch_mplsh_probes, probe_valid_mask
        hk_off = batch_mplsh_probes(params, cfg, queries, cfg.L)
        pvalid = probe_valid_mask(hk_off)
    else:
        offs = batch_query_offsets(base_key, qids, queries, cfg.L, cfg.r)
        hk_off = hash_h(params, offs, cfg.W)           # (m, L, k)
        pvalid = jnp.ones(hk_off.shape[:2], bool)
    return hk_off, pvalid


def simulate(cfg: LSHConfig, data: jax.Array, queries: jax.Array,
             compute_recall: bool = False,
             data_chunk: int = 4096,
             k_neighbors: Optional[int] = None) -> accounting.TrafficReport:
    """Run the full accounting for one scheme on one dataset.

    Args:
      data: (n, d) float32 data points.
      queries: (m, d) float32 query points.
      compute_recall: if True, run the exact (chunked) candidate search and
        report the paper's recall metric (>=1 point within r returned).
        With n_tables > 1 the candidate set is the union over tables.
      k_neighbors: additionally report recall@K (fraction of the exact
        brute-force top-K retrieved by the LSH candidate top-K within cr)
        -- requires compute_recall=True.
    """
    sim = make_sim(cfg)
    n, d = data.shape
    m = queries.shape[0]
    S, T = cfg.n_shards, cfg.n_tables
    qids = jnp.arange(m, dtype=jnp.int32)

    data_load = np.zeros((S,), np.int64)
    query_load = np.zeros((S,), np.int64)
    fq = np.zeros((m,), np.int64)
    q_rows_t, d_rows_t = [], []
    probes_t: list = []          # per-table (hk_off, pvalid) for recall

    # index build: one row per point per table, hashed in one stacked pass
    data_shard_T = _data_shards(sim, data)             # (T, n)
    for t in range(T):
        params = sim.table_params[t]
        data_load += np.bincount(data_shard_T[t], minlength=S)
        d_rows_t.append(n)

        # ------------- query routing -----------------------------------
        hk_off, pvalid = _probe_hashes(sim, queries, qids, table=t)
        probes_t.append((hk_off, pvalid))
        keys_off = shard_key(params, cfg, hk_off)      # (m, L) int32
        if cfg.scheme == Scheme.SIMPLE:
            # one pair per distinct H-bucket (the Key is the bucket id)
            packed_off = pack_buckets(params, hk_off)  # (m, L, 2)
            live = _dedupe_mask_packed(packed_off) & pvalid
        else:
            # one pair per distinct GH value
            live = _dedupe_mask_2d(keys_off) & pvalid
        dest = jnp.mod(keys_off, S).astype(jnp.int32)  # (m, L)

        live_np = np.asarray(live)
        dest_np = np.asarray(dest)
        query_load += np.bincount(dest_np[live_np], minlength=S)
        fq += np.asarray(live.sum(axis=1))
        q_rows_t.append(int(live_np.sum()))

    query_rows = int(sum(q_rows_t))
    report = accounting.TrafficReport(
        scheme=cfg.scheme.value,
        n_shards=S,
        query_rows=query_rows,
        query_bytes=query_rows * accounting.query_row_bytes(d, T),
        fq_mean=float(fq.mean()),
        fq_max=int(fq.max()),
        fq_bound=cfg.fq_bound(),
        data_rows=n * T,
        data_bytes=n * T * accounting.data_row_bytes(d, T),
        data_load_avg=float(data_load.mean()),
        data_load_max=int(data_load.max()),
        query_load_avg=float(query_load.mean()),
        query_load_max=int(query_load.max()),
        n_tables=T,
        query_rows_by_table=tuple(q_rows_t),
        data_rows_by_table=tuple(d_rows_t),
    )

    if compute_recall:
        rec, emitted, _, lsh_idx = _exact_search_recall(
            cfg, sim.table_params, data, queries, probes_t, data_chunk,
            k=k_neighbors)
        report.recall = rec
        report.results_emitted = emitted
        if k_neighbors:
            from repro.core.ref_search import nearest_neighbors
            _, true_idx = nearest_neighbors(np.asarray(data),
                                            np.asarray(queries), k_neighbors)
            report.recall_at_k = recall_at_k(lsh_idx, true_idx)
            report.k_neighbors = k_neighbors
    return report


def recall_at_k(retrieved: np.ndarray, truth: np.ndarray) -> float:
    """Mean per-query |retrieved top-K ∩ exact top-K| / K (the survey's
    recall@K).  Sentinel (IMAX) entries never match real indices."""
    m, k = truth.shape
    overlap = (retrieved[:, :, None] == truth[:, None, :]).any(axis=1)
    imax = np.iinfo(np.int32).max
    valid = truth != imax
    return float((overlap & valid).sum(axis=1).mean() / k)


def lsh_topk_reference(cfg: LSHConfig, data: jax.Array, queries: jax.Array,
                       k: int, data_chunk: int = 4096
                       ) -> tuple[np.ndarray, np.ndarray]:
    """Single-machine LSH top-K ground truth: for each query, the exact K
    best (dist, gid) pairs among its LSH candidate set (points whose
    H-bucket matches a probed bucket in ANY of the n_tables tables)
    within distance cr, in the same (dist, gid) lex order as the
    distributed path -- what the sharded fused index must reproduce
    regardless of placement scheme or table count.

    Returns (m, k) sqrt-distances (inf pad) and gids (IMAX pad).
    """
    sim = make_sim(cfg)
    qids = jnp.arange(queries.shape[0], dtype=jnp.int32)
    probes_t = [_probe_hashes(sim, queries, qids, table=t)
                for t in range(cfg.n_tables)]
    _, _, topd, topg = _exact_search_recall(
        cfg, sim.table_params, data, queries, probes_t, data_chunk, k=k)
    return topd, topg


@dataclasses.dataclass
class StreamReport:
    """Steady-state accounting for a streaming insert+query mix.

    The paper's two figures of merit (shuffle size, max reducer load)
    measured in the serving regime: the index grows online while query
    buckets flush against the current store, so load balance and traffic
    are trajectories, not single numbers.  Rows sum over the fused
    tables.
    """
    scheme: str
    n_shards: int
    steps: int
    total_inserted: int
    total_queries: int
    # ---- traffic (per step: live routed rows) ----
    query_rows_per_step: np.ndarray    # (steps,)
    insert_rows_per_step: np.ndarray   # (steps,)
    fq_mean: float                     # rows/query over the whole stream
    # ---- load balance trajectories (max/avg skew per step) ----
    data_skew: np.ndarray              # (steps,) store skew after insert
    query_skew: np.ndarray             # (steps,) query-shard skew per step
    data_load_final: np.ndarray        # (S,) live rows at end of stream
    n_tables: int = 1

    @property
    def data_skew_final(self) -> float:
        avg = max(float(self.data_load_final.mean()), 1.0)
        return float(self.data_load_final.max()) / avg

    def summary(self) -> str:
        return (f"scheme={self.scheme} shards={self.n_shards} "
                f"tables={self.n_tables} "
                f"steps={self.steps} inserted={self.total_inserted} "
                f"queries={self.total_queries} "
                f"rows/query={self.fq_mean:.2f} "
                f"data skew final={self.data_skew_final:.2f} "
                f"(per-step max {self.data_skew.max():.2f}) "
                f"query skew mean={self.query_skew.mean():.2f}")


def simulate_stream(cfg: LSHConfig, data: jax.Array, queries: jax.Array,
                    n_prefix: int, insert_batch: int,
                    query_batch: int) -> StreamReport:
    """Analytic streaming mix: build on data[:n_prefix], then per step
    insert the next ``insert_batch`` rows and answer ``query_batch``
    queries (cycling through ``queries``) against the grown store.

    Query ids restart per bucket -- exactly what the serving front-end's
    pad-to-bucket flush does -- so per-step traffic matches the service.
    Inserted-row counts are POINTS (the fused index stores n_tables rows
    per point; loads below count rows, matching ``shard_load``).
    """
    sim = make_sim(cfg)
    n = data.shape[0]
    m_all = queries.shape[0]
    S, T = cfg.n_shards, cfg.n_tables

    data_shard_t = _data_shards(sim, data)   # (T, n) shard ids
    load = np.zeros((S,), np.int64)
    for t in range(T):
        load += np.bincount(data_shard_t[t][:n_prefix], minlength=S)

    qids = jnp.arange(query_batch, dtype=jnp.int32)
    steps = max(1, (n - n_prefix) // max(insert_batch, 1))
    q_rows, i_rows, d_skew, q_skew = [], [], [], []
    total_q = 0
    fq_sum = 0.0
    for step in range(steps):
        lo = n_prefix + step * insert_batch
        hi = min(n, lo + insert_batch)
        for t in range(T):
            load += np.bincount(data_shard_t[t][lo:hi], minlength=S)
        i_rows.append(hi - lo)
        d_skew.append(load.max() / max(load.mean(), 1.0))

        sel = (np.arange(query_batch) + step * query_batch) % m_all
        q = queries[jnp.asarray(sel)]
        step_rows = 0
        qload = np.zeros((S,), np.int64)
        for t in range(T):
            params = sim.table_params[t]
            offs = batch_query_offsets(sim.table_keys[t], qids, q,
                                       cfg.L, cfg.r)
            hk_off = hash_h(params, offs, cfg.W)
            keys_off = shard_key(params, cfg, hk_off)
            if cfg.scheme == Scheme.SIMPLE:
                live = _dedupe_mask_packed(pack_buckets(params, hk_off))
            else:
                live = _dedupe_mask_2d(keys_off)
            live_np = np.asarray(live)
            dest_np = np.asarray(jnp.mod(keys_off, S).astype(jnp.int32))
            qload += np.bincount(dest_np[live_np], minlength=S)
            step_rows += int(live_np.sum())
        q_rows.append(step_rows)
        q_skew.append(qload.max() / max(qload.mean(), 1.0))
        fq_sum += float(step_rows)
        total_q += query_batch

    return StreamReport(
        scheme=cfg.scheme.value, n_shards=S, steps=steps,
        total_inserted=int(sum(i_rows)), total_queries=total_q,
        query_rows_per_step=np.asarray(q_rows),
        insert_rows_per_step=np.asarray(i_rows),
        fq_mean=fq_sum / max(total_q, 1),
        data_skew=np.asarray(d_skew), query_skew=np.asarray(q_skew),
        data_load_final=load, n_tables=T)


def _exact_search_recall(cfg: LSHConfig, table_params: List[HashParams],
                         data: jax.Array, queries: jax.Array,
                         probes_t: list, data_chunk: int,
                         k: Optional[int] = None
                         ) -> tuple[float, int,
                                    Optional[np.ndarray],
                                    Optional[np.ndarray]]:
    """Chunked exact candidate search (single pass over the data).

    A data point p is a candidate for query q iff H_t(p) equals
    H_t(q+delta^t_i) for some table t and live offset i of that table
    (note: placement scheme does NOT change the candidate set -- GH is a
    function of H, so bucket-mates are always co-located with the routed
    query row).  ``probes_t`` is a list of per-table (hk_off, pvalid)
    pairs as produced by ``_probe_hashes``.  Returns
      (recall, emitted, topk_dist, topk_gid):
    recall = fraction of queries for which a returned candidate lies
    within distance r; emitted = total (candidate, table) hits within cr
    -- a point co-bucketed in several tables counts once per table,
    matching the distributed path's n_within_cr; with k set, also the
    per-query exact top-K among candidates within cr, as (m, k)
    sqrt-distances / gids in (dist, gid) lex order (else None, None).
    """
    from repro.core.ref_search import (sq_dists, topk_merge_host,
                                       topk_sort_jnp)
    T = len(probes_t)
    m = probes_t[0][0].shape[0]
    packed_off_t = [pack_buckets(table_params[t], probes_t[t][0])
                    for t in range(T)]                 # (m, L, 2) each
    r2 = jnp.float32(cfg.r ** 2)
    cr2 = jnp.float32((cfg.c * cfg.r) ** 2)
    imax = np.iinfo(np.int32).max

    def chunk_stats(chunk: jax.Array, packed_chunk_t: tuple, idx0):
        # (m, B) candidate mask per table; emit counts sum over tables
        cand_any = jnp.zeros((m, chunk.shape[0]), bool)
        n_hit_tables = jnp.zeros((m, chunk.shape[0]), jnp.int32)
        for t in range(T):
            eq = jnp.all(
                packed_off_t[t][:, :, None, :] == packed_chunk_t[t][None, None],
                axis=-1)                               # (m, L, B)
            cand_t = jnp.any(eq & probes_t[t][1][:, :, None], axis=1)
            cand_any = cand_any | cand_t
            n_hit_tables = n_hit_tables + cand_t.astype(jnp.int32)
        d2 = sq_dists(queries, chunk)
        within = d2 <= cr2
        hit = cand_any & within
        hit_r = jnp.any(cand_any & (d2 <= r2), axis=1)  # (m,)
        emit = jnp.sum(jnp.where(within, n_hit_tables, 0))
        if not k:
            return hit_r, emit, (), ()
        cd = jnp.where(hit, d2, jnp.inf)
        cg = jnp.where(hit, idx0 + jnp.arange(chunk.shape[0],
                                              dtype=jnp.int32)[None, :],
                       imax)
        return hit_r, emit, *topk_sort_jnp(cd, cg, k)

    chunk_stats = jax.jit(chunk_stats)
    hits = np.zeros((m,), dtype=bool)
    emitted = 0
    best = np.full((m, k), np.inf, np.float32) if k else None
    arg = np.full((m, k), imax, np.int32) if k else None
    n = data.shape[0]
    packed_data_t = tuple(
        pack_buckets(table_params[t], hash_h(table_params[t], data, cfg.W))
        for t in range(T))
    for s in range(0, n, data_chunk):
        e = min(n, s + data_chunk)
        h, em, cd, cg = chunk_stats(
            data[s:e], tuple(p[s:e] for p in packed_data_t), np.int32(s))
        hits |= np.asarray(h)
        emitted += int(em)
        if k:
            best, arg = topk_merge_host(best, arg, cd, cg)
    return (float(hits.mean()), emitted,
            np.sqrt(best) if k else None, arg)
