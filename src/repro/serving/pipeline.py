"""Double-buffered micro-batch query pipeline over the staged index.

``DistributedLSHIndex`` exposes the query step as three separately-
invocable stages (``query_dispatch`` / ``query_scan`` / ``query_return``)
cut exactly at its two all_to_all boundaries.  jax dispatch is
asynchronous -- each stage call only ENQUEUES device work and returns
handles -- so submitting batch i+1's stages right after batch i's lines
both up on the device stream:

    batch i   : dispatch a2a | bucket scan  | return a2a + merge
    batch i+1 :              | dispatch a2a | bucket scan | return ...

i+1's dispatch all_to_all overlaps i's bucket-gather scan, and the host
side (staging the next bucket, fetching a retired bucket's results)
overlaps device compute entirely.  The host blocks in exactly one place:
``retire_one`` fetching the oldest in-flight batch's outputs.

Two staging slots rotate because the dispatch stage DONATES its query
buffer: slot s is refilled only after the batch that staged through s has
retired, so a donated buffer is never scribbled while a compiled stage
may still read it.  ``depth`` in-flight batches therefore need ``depth``
slots (default 2 -- classic double buffering).

Results are bitwise identical to the synchronous ``flush`` path: its
``query()`` runs the same three compiled stage programs back to back,
and retirement applies the same numpy post-processing in the same
submission order (tested in tests/test_serving_pipeline.py).

Spans (``core/spans.py``; args ``batch`` = the submit sequence number):
``lsh.enqueue`` covers staging plus the three stage calls, ``lsh.retire``
the fetch (``lsh.fetch``, where the host waits on the device) and the
handle loop (``lsh.resolve``).  The scan's counters come back with the
fetch the retire makes anyway, so counting adds no host sync.
"""
from __future__ import annotations

import time
from collections import deque
from typing import List, Optional

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.index import DistributedLSHIndex
from repro.core.spans import span
from repro.serving.service import BatchRecord, ServiceStats


@dataclasses.dataclass
class _InFlight:
    """One submitted micro-batch: device handles + its query handles."""
    handles: list                 # per-query handle objects (resolved late)
    topd: jax.Array               # (bucket, K) squared dists (device)
    topg: jax.Array               # (bucket, K) gids (device)
    emit: jax.Array               # (bucket,) emit counts (device)
    fq: jax.Array                 # (bucket,) routed rows (device)
    drops: jax.Array              # (S,) capacity drops (device)
    counters: jax.Array           # (S, C) what the scan saw (device)
    seq: int                      # submit sequence number
    take: int                     # live queries (rest is padding)
    reason: str                   # what triggered the submit (stats key)
    t_enqueue: float              # pipeline clock when staging began
    t_enqueued: float             # ... when the three stages were enqueued


class QueryPipeline:
    """Depth-bounded in-flight query batches over the staged index.

    ``submit`` stages one bucket and enqueues all three stages (never
    blocks on device work; it retires the oldest batch first if the
    pipeline is full).  ``retire_one``/``drain`` fetch results and
    resolve handles.  Handle objects need the ``PendingQuery`` attribute
    surface (gids/dists/gid/dist/n_within_cr/fq/batch/done/t_submit) plus
    an optional ``_resolved()`` hook (used by the async front-end to wake
    waiters).
    """

    def __init__(self, index: DistributedLSHIndex, bucket_size: int,
                 k_neighbors: Optional[int] = None, depth: int = 2,
                 clock=time.monotonic,
                 stats: Optional[ServiceStats] = None):
        S = index.cfg.n_shards
        if bucket_size % S:
            raise ValueError(
                f"bucket_size={bucket_size} must divide by n_shards={S}")
        if depth < 1:
            raise ValueError(f"depth={depth} must be >= 1")
        self.index = index
        self.bucket_size = bucket_size
        self.k_neighbors = (index.k_neighbors if k_neighbors is None
                            else k_neighbors)
        self.depth = depth
        self.stats = ServiceStats() if stats is None else stats
        self._clock = clock
        # one staging slot per in-flight batch: a slot is reused only
        # after its batch retired (donation safety; see module docstring)
        self._slots = [np.zeros((bucket_size, index.cfg.d), np.float32)
                       for _ in range(depth)]
        self._slot = 0
        self._seq = 0
        self._inflight: deque[_InFlight] = deque()

    @property
    def n_inflight(self) -> int:
        return len(self._inflight)

    def submit(self, rows: List[np.ndarray], handles: list,
               reason: str = "manual") -> None:
        """Stage one bucket (<= bucket_size rows) and enqueue its stages.

        rows[i] is handle[i]'s (d,) float32 query.  Shorter-than-bucket
        submissions are zero-padded (the compiled stages are shape-
        specialised to the bucket).  Returns immediately after enqueuing
        the device work -- blocks only to retire the oldest batch when
        ``depth`` batches are already in flight.
        """
        take = len(handles)
        if not 0 < take <= self.bucket_size:
            raise ValueError(f"got {take} handles for bucket_size="
                             f"{self.bucket_size}")
        while len(self._inflight) >= self.depth:
            self.retire_one()
        seq = self._seq
        t0 = self._clock()
        with span("lsh.enqueue", batch=seq, reason=reason, take=take):
            buf = self._slots[self._slot]
            buf[:take] = rows
            buf[take:] = 0.0   # re-zero the pad region (slot is reused)
            disp = self.index.query_dispatch(jnp.asarray(buf), donate=True,
                                             batch=seq)
            scanned = self.index.query_scan(disp,
                                            k_neighbors=self.k_neighbors)
            topd, topg, emit = self.index.query_return(scanned)
        self._inflight.append(_InFlight(
            handles=handles, topd=topd, topg=topg, emit=emit,
            fq=disp.fq, drops=disp.drops, counters=scanned.counters,
            seq=seq, take=take, reason=reason, t_enqueue=t0,
            t_enqueued=self._clock()))
        self._seq += 1
        self._slot = (self._slot + 1) % self.depth
        if len(self._inflight) > self.stats.inflight_peak:
            self.stats.inflight_peak = len(self._inflight)

    def retire_one(self) -> int:
        """Fetch + resolve the OLDEST in-flight batch (blocks on device).

        Returns the number of live queries answered (0 if none in
        flight).  Handle resolution is bit-identical to the synchronous
        flush: same sqrt/inf conversion, same per-handle numpy slices.
        """
        if not self._inflight:
            return 0
        fl = self._inflight.popleft()
        with span("lsh.retire", batch=fl.seq):
            with span("lsh.fetch", batch=fl.seq):
                topd = np.asarray(fl.topd)  # blocks until the batch ran
                topg = np.asarray(fl.topg)
                emit = np.asarray(fl.emit)
                fq = np.asarray(fl.fq).reshape(-1)
                drops = int(np.asarray(fl.drops).sum())
                counters = np.asarray(fl.counters)
            now = self._clock()
            dists = np.sqrt(np.where(topd < np.float32(3e38), topd, np.inf))
            rec = BatchRecord(
                seq=fl.seq, reason=fl.reason, take=fl.take,
                pad=self.bucket_size - fl.take, drops=drops,
                routed_rows=int(fq[:fl.take].sum()),
                admission_wait_s=sum(fl.t_enqueue - h.t_submit
                                     for h in fl.handles),
                t_enqueue=fl.t_enqueue, t_enqueued=fl.t_enqueued,
                counters=counters)
            st = self.stats
            st.record_batch(rec)
            with span("lsh.resolve", batch=fl.seq):
                for i, h in enumerate(fl.handles):
                    h.gids = topg[i].copy()
                    h.dists = dists[i].copy()
                    h.gid = int(h.gids[0])
                    h.dist = float(h.dists[0])
                    h.n_within_cr = int(emit[i])
                    h.fq = int(fq[i])
                    h.batch = rec
                    h.done = True
                    st.record_latency((now - h.t_submit) * 1e3)
                    resolved = getattr(h, "_resolved", None)
                    if resolved is not None:
                        resolved()
        return fl.take

    def drain(self) -> int:
        """Retire every in-flight batch; returns total queries answered."""
        total = 0
        while self._inflight:
            total += self.retire_one()
        return total
