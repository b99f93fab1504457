"""Typed call surface for the bucket-search kernels.

The old ``ops.bucket_search`` took 10+ positional arrays; adding the CSR
bucket offsets would have pushed it past a dozen.  These two frozen
pytree dataclasses replace that signature: a ``QueryBatch`` bundles the
per-row probe state, a ``StoreView`` bundles one shard's stored rows --
including the optional CSR layout (``bucket_start``/``bucket_end`` +
static ``n_sorted``) that the bucket-gather kernel consumes.  Both are
registered pytrees, so they pass through ``jax.jit``/``shard_map``
boundaries like plain arrays.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class QueryBatch:
    """One shard's received query rows, ready for the bucket scan.

    buckets holds the packed (hi, lo) pair of each of the L probed
    offset buckets, flattened to 2*L int32 words per row (the bitcast
    uint32 packing -- equality of int32 words == equality of buckets).
    """

    q: jax.Array        # (R, d) float32 query rows
    qsq: jax.Array      # (R,) float32 squared norms
    buckets: jax.Array  # (R, 2*L) int32 packed probe buckets
    probe: jax.Array    # (R, L) int32 0/1 -- probe this bucket?
    table: jax.Array    # (R,) int32 table id each row probes

    @classmethod
    def build(cls, q, buckets, probe, table=None) -> "QueryBatch":
        """Convenience constructor: computes qsq, defaults table to 0."""
        if table is None:
            table = jnp.zeros((q.shape[0],), jnp.int32)
        return cls(q=q, qsq=jnp.sum(q.astype(jnp.float32) ** 2, axis=-1),
                   buckets=buckets, probe=probe, table=table)

    @property
    def n_probes(self) -> int:
        return self.probe.shape[1]

    def tree_flatten(self):
        return ((self.q, self.qsq, self.buckets, self.probe, self.table),
                None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class StoreView:
    """One shard's stored rows as the kernels see them.

    Layout contract: rows ``[0, n_sorted)`` are sorted by (table, packed
    hi, packed lo) with per-row CSR spans -- ``bucket_start[i]`` /
    ``bucket_end[i]`` delimit the row range of row i's own bucket inside
    the sorted region.  Rows ``[n_sorted, N)`` are the unsorted insert
    tail, scanned by the full-scan kernel.  ``n_sorted == 0`` marks a
    fully unsorted store (the pre-CSR layout); the CSR arrays may then
    be None and every consumer falls back to the full scan.

    ``extent`` (static) is the store's live extent: no row at or past it
    is valid, so the kernels' full scans stop there (None = all N rows).
    The jnp oracle ignores ``extent`` and reads every row.
    """

    points: jax.Array   # (N, d) float32 stored points
    psq: jax.Array      # (N,) float32 squared norms
    buckets: jax.Array  # (N, 2) int32 packed H bucket per row
    gid: jax.Array      # (N,) int32 global ids (IMAX = empty)
    valid: jax.Array    # (N,) int32 0/1 liveness
    table: jax.Array    # (N,) int32 table id per row
    key: Optional[jax.Array] = None           # (N,) int32 routing Key
    bucket_start: Optional[jax.Array] = None  # (N,) int32 CSR span start
    bucket_end: Optional[jax.Array] = None    # (N,) int32 CSR span end
    n_sorted: int = 0   # static: rows [0, n_sorted) are bucket-sorted
    extent: Optional[int] = None   # static: no valid row at or past it

    @classmethod
    def build(cls, points, buckets, gid, valid, table=None, key=None,
              bucket_start=None, bucket_end=None,
              n_sorted: int = 0) -> "StoreView":
        """Convenience constructor: computes psq, defaults table to 0."""
        if table is None:
            table = jnp.zeros((points.shape[0],), jnp.int32)
        return cls(points=points,
                   psq=jnp.sum(points.astype(jnp.float32) ** 2, axis=-1),
                   buckets=buckets, gid=gid, valid=valid, table=table,
                   key=key, bucket_start=bucket_start,
                   bucket_end=bucket_end, n_sorted=n_sorted)

    @property
    def n_rows(self) -> int:
        return self.points.shape[0]

    def tree_flatten(self):
        return ((self.points, self.psq, self.buckets, self.gid, self.valid,
                 self.table, self.key, self.bucket_start, self.bucket_end),
                (self.n_sorted, self.extent))

    @classmethod
    def tree_unflatten(cls, aux, children):
        n_sorted, extent = aux
        return cls(*children, n_sorted=n_sorted, extent=extent)
