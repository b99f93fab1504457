"""Public wrappers for the Pallas kernels.

Each op pads inputs to kernel tile multiples, dispatches to the Pallas
kernel (compiled by Mosaic on a TPU; interpret=True on the CPU backend,
where the tests validate it), and unpads. ``use_kernel=False``
falls back to the jnp oracle, which the dry-run / XLA path also uses for
sharded lowering.

``bucket_search`` takes the typed ``QueryBatch``/``StoreView`` call
surface (keyword-only) and dispatches on the store's layout: a
bucket-sorted store (``n_sorted > 0``) routes through the CSR
bucket-gather kernel -- per-probe span lookup by binary search, probe
expansion sorted by span start, windowed aligned-tile gather -- plus a
full scan of the unsorted insert tail; anything else takes the full-scan
kernel.  The CSR path's results are bitwise identical to the full scan
(same dot_general tiles, same exact top-K selection), and a traced
overflow guard falls back to the full scan whenever a row tile's spans
do not fit the static window, so correctness never depends on the
window budget.  Every full scan -- the tail's, the fallback's and the
unsorted store's -- stops at the store's live extent (``StoreView.extent``),
past which no row is valid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.bucket_search import (TILE_N, TILE_R,
                                         bucket_gather_pallas,
                                         bucket_search_pallas)
from repro.kernels.flash_attention import (TILE_K, TILE_Q,
                                           flash_attention_pallas)
from repro.kernels.lsh_hash import LANE, TILE_N as HASH_TILE_N, lsh_hash_pallas
from repro.kernels.ssd_scan import CHUNK, ssd_scan_pallas
from repro.kernels.types import QueryBatch, StoreView

F32_MAX = float(jnp.finfo(jnp.float32).max)
IMAX = int(jnp.iinfo(jnp.int32).max)

# default CSR gather window (aligned store tiles per row tile) when the
# caller has no bucket statistics to size it from
DEFAULT_WINDOW_TILES = 4

# the counters ``bucket_search`` returns, in order (see its docstring):
# the CSR overflow guard's five, then what the taken path returned
GUARD_COUNTERS = ("fallback", "need_tiles", "window_tiles", "probes_live",
                  "probes_expanded")
SCAN_COUNTERS = GUARD_COUNTERS + ("hits", "filled", "grid_steps",
                                  "merge_passes")


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _pad_to(x: jax.Array, axis: int, mult: int, value=0) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ---------------------------------------------------------------------------

def lsh_hash(x: jax.Array, a: jax.Array, b: jax.Array, *, w: float,
             use_kernel: bool = True) -> jax.Array:
    """Fused floor((x@a+b)/w) -> int32 (n, k)."""
    if not use_kernel:
        return ref.lsh_hash_ref(x, a, b, w=w)
    n, k = x.shape[0], a.shape[1]
    xp = _pad_to(x, 0, HASH_TILE_N)
    ap = _pad_to(a, 1, LANE)
    bp = _pad_to(b, 0, LANE)
    out = lsh_hash_pallas(xp, ap, bp, w=w, interpret=_on_cpu())
    return out[:n, :k]


# ---------------------------------------------------------------------------
# bucket_search: typed call surface + CSR/full-scan dispatch
# ---------------------------------------------------------------------------

def _pad_query(query: QueryBatch) -> QueryBatch:
    """Pad the row axis to TILE_R (padded rows probe nothing)."""
    return QueryBatch(q=_pad_to(query.q, 0, TILE_R),
                      qsq=_pad_to(query.qsq, 0, TILE_R),
                      buckets=_pad_to(query.buckets, 0, TILE_R),
                      probe=_pad_to(query.probe, 0, TILE_R),
                      table=_pad_to(query.table, 0, TILE_R))


def _pad_slice(store: StoreView, lo: int, hi: int) -> StoreView:
    """Row slice [lo, hi) of a StoreView, padded to TILE_N (padded points
    invalid, gid = IMAX).  The CSR fields are dropped -- padded views
    feed the layout-agnostic kernels only."""
    sl = lambda a: a[lo:hi]
    return StoreView(
        points=_pad_to(sl(store.points), 0, TILE_N),
        psq=_pad_to(sl(store.psq), 0, TILE_N),
        buckets=_pad_to(sl(store.buckets), 0, TILE_N),
        gid=_pad_to(sl(store.gid), 0, TILE_N, value=IMAX),
        valid=_pad_to(sl(store.valid), 0, TILE_N),
        table=_pad_to(sl(store.table), 0, TILE_N))


def csr_probe_spans(query: QueryBatch, store: StoreView
                    ) -> tuple[jax.Array, jax.Array]:
    """Per-probe CSR spans: (start, end) (R, L) int32 row ranges of each
    probed bucket inside the sorted region [0, n_sorted).

    Vectorised branchless lower-bound binary search over the store's lex
    (table, packed hi, packed lo) sort order (uint32 bucket words, the
    same order ``load_rows`` sorts by) locates ``start``; the span end is
    read straight from the store's per-row CSR column (``bucket_end`` of
    the first row in the bucket) when present, else found by a second
    upper-bound search.  Probes that are off, or whose bucket is absent
    on this shard, get the empty span start == end.  Sentinel padding
    rows inside the sorted region (table == IMAX) sort after every real
    probe and can never match.
    """
    R, L = query.probe.shape
    ns = store.n_sorted
    if ns == 0:
        z = jnp.zeros((R, L), jnp.int32)
        return z, z
    st = store.table[:ns]
    sb = jax.lax.bitcast_convert_type(store.buckets[:ns], jnp.uint32)
    sh, sl = sb[:, 0], sb[:, 1]
    qb = jax.lax.bitcast_convert_type(
        query.buckets.reshape(R, L, 2), jnp.uint32)
    qh, ql = qb[..., 0], qb[..., 1]
    qt = jnp.broadcast_to(query.table[:, None], (R, L))

    def less(idx, or_equal):
        """store row[idx] <(=) probe triple, elementwise over (R, L)."""
        i = jnp.clip(idx, 0, ns - 1)
        t, h, l = st[i], sh[i], sl[i]
        lt = (t < qt) | ((t == qt) & ((h < qh) | ((h == qh) & (l < ql))))
        if or_equal:
            lt = lt | ((t == qt) & (h == qh) & (l == ql))
        return lt

    def count(or_equal):
        """Number of sorted rows <(=) each probe (== lower/upper bound)."""
        lo = jnp.zeros((R, L), jnp.int32)
        step = 1 << (ns - 1).bit_length()
        while step:
            cand = lo + step
            ok = (cand <= ns) & less(cand - 1, or_equal)
            lo = jnp.where(ok, cand, lo)
            step //= 2
        return lo

    start = count(False)
    if store.bucket_end is not None:
        i = jnp.clip(start, 0, ns - 1)
        matched = ((start < ns) & (st[i] == qt) & (sh[i] == qh)
                   & (sl[i] == ql))
        end = jnp.where(matched, store.bucket_end[:ns][i], start)
    else:
        end = count(True)
    on = query.probe > 0
    zero = jnp.zeros((), jnp.int32)
    return jnp.where(on, start, zero), jnp.where(on, end, zero)


def _extent(store: StoreView) -> int:
    """Rows [0, extent) the full scans visit, at least one: no valid
    row lies past them."""
    n = store.n_rows
    return n if store.extent is None else min(max(store.extent, 1), n)


def _full_scan(query_p: QueryBatch, store: StoreView, lo: int, hi: int,
               cr2, *, L: int, k: int, interpret: bool,
               name: str = "bucket_search_pallas"):
    """Full-scan kernel over store rows [lo, hi), hi > lo.  From row 0,
    whole tiles that stay inside the store are scanned in place, only
    the kernel's grid bounded; anything else scans a padded copy of the
    slice (padded rows invalid)."""
    tiles = -(-(hi - lo) // TILE_N)
    view = (store if lo == 0 and tiles * TILE_N <= store.n_rows
            else _pad_slice(store, lo, hi))
    return bucket_search_pallas(query=query_p, store=view, cr2=cr2, L=L,
                                K=k, interpret=interpret, name=name,
                                n_tiles=tiles)


def _grid_steps(rows: int, points: int) -> int:
    """(row tile, store tile) grid steps of one kernel call over ``rows``
    query rows and ``points`` store rows, both padded to whole tiles."""
    return -(-rows // TILE_R) * -(-points // TILE_N)


def _counters(guard, cnt, topg, grid_steps, passes) -> jax.Array:
    """The ``SCAN_COUNTERS`` vector: the guard's five, then the hits
    within cr, the top-K ranks that hold a gid, the kernels' grid steps
    and their top-K merge's insertion passes, read off what the taken
    path returned."""
    return jnp.concatenate([guard, jnp.stack([
        cnt.sum(dtype=jnp.int32), (topg != IMAX).sum(dtype=jnp.int32),
        jnp.asarray(grid_steps, jnp.int32),
        jnp.asarray(passes, jnp.int32)])])


def _no_guard() -> jax.Array:
    return jnp.zeros((len(GUARD_COUNTERS),), jnp.int32)


def _csr_search(query: QueryBatch, query_p: QueryBatch, store: StoreView,
                cr2, *, L: int, k: int, window_tiles: int,
                interpret: bool):
    """CSR path: span lookup -> sorted probe expansion -> windowed gather
    over the sorted region + full scan of the tail, exact-merged."""
    R = query.q.shape[0]
    ns, ext = store.n_sorted, _extent(store)
    n_tiles = -(-ns // TILE_N)
    G = max(1, min(window_tiles, n_tiles))

    # ---- per-probe spans, expanded rows sorted by span start so each
    # 128-row tile's spans cluster into a small tile window ----
    with jax.named_scope("spans"):
        start, end = csr_probe_spans(query, store)
    # Duplicate probes of one row (two perturbations packing to the same
    # bucket) must count each store row once, as the full scan's OR-mask
    # does.  Identical non-empty spans identify identical buckets, so
    # blank every repeat after the first.
    with jax.named_scope("expand"):
        if L > 1:
            dup_cols = [jnp.zeros((R,), bool)]
            for l in range(1, L):
                d_l = jnp.zeros((R,), bool)
                for m in range(l):
                    d_l = d_l | ((start[:, l] == start[:, m])
                                 & (end[:, l] == end[:, m]))
                dup_cols.append(d_l)
            dup = jnp.stack(dup_cols, axis=1) & (end > start)
            zero = jnp.zeros((), jnp.int32)
            start = jnp.where(dup, zero, start)
            end = jnp.where(dup, zero, end)
        sflat, eflat = start.reshape(-1), end.reshape(-1)
        E0 = R * L
        live = eflat > sflat
        order = jnp.argsort(jnp.where(live, sflat, ns))   # dead probes last
        E = -(-E0 // TILE_R) * TILE_R
        rowid = order // L
        eq = _pad_to(query.q[rowid], 0, TILE_R)
        eqsq = _pad_to(query.qsq[rowid], 0, TILE_R)
        es = _pad_to(sflat[order], 0, TILE_R)
        ee = _pad_to(eflat[order], 0, TILE_R)             # pad: empty spans
        elive = _pad_to(live[order], 0, TILE_R)

        # ---- static-window bases + overflow guard ----
        lo_t = jnp.where(elive, es // TILE_N, n_tiles - 1).astype(jnp.int32)
        hi_t = jnp.where(elive, (ee - 1) // TILE_N, 0).astype(jnp.int32)
        base = jnp.min(lo_t.reshape(-1, TILE_R), axis=1)
        need = jnp.max(hi_t.reshape(-1, TILE_R) - base[:, None] + 1, axis=1)
        overflow = jnp.any(need > G)
        base = jnp.clip(base, 0, n_tiles - G)
        # what the guard saw, reported whichever branch runs
        guard = jnp.stack([
            overflow.astype(jnp.int32), jnp.maximum(jnp.max(need), 0),
            jnp.int32(G), live.sum(dtype=jnp.int32), jnp.int32(E)])

    sorted_view = _pad_slice(store, 0, ns)

    def run_csr(_):
        with jax.named_scope("gather"):
            gd, gg, gc, passes = bucket_gather_pallas(
                base, eq, eqsq, es, ee, sorted_view.points,
                sorted_view.psq, sorted_view.gid, sorted_view.valid, cr2,
                K=k, G=G, interpret=interpret)
        with jax.named_scope("merge"):
            # unsort back to (row, probe) order; spans of one row's probes
            # are disjoint buckets, so a plain lex sort merges them exactly
            rd = jnp.full((E0, k), F32_MAX, jnp.float32).at[order].set(
                gd[:E0])
            rg = jnp.full((E0, k), IMAX, jnp.int32).at[order].set(gg[:E0])
            rc = jnp.zeros((E0,), jnp.int32).at[order].set(gc[:E0])
            cand_d = rd.reshape(R, L * k)
            cand_g = rg.reshape(R, L * k)
            cnt = rc.reshape(R, L).sum(axis=1)
        if ext > ns:                       # unsorted insert tail
            with jax.named_scope("tail"):
                td, tg, tc, tp = _full_scan(
                    query_p, store, ns, ext, cr2, L=L, k=k,
                    interpret=interpret, name="bucket_search_pallas_tail")
            cand_d = jnp.concatenate([cand_d, td[:R]], axis=1)
            cand_g = jnp.concatenate([cand_g, tg[:R]], axis=1)
            cnt = cnt + tc[:R]
            passes = passes + tp
        with jax.named_scope("merge"):
            sd, sg = jax.lax.sort((cand_d, cand_g), dimension=1,
                                  num_keys=2)
        return sd[:, :k], sg[:, :k], cnt, passes

    def run_full(_):
        with jax.named_scope("fallback"):
            td, tg, tc, tp = _full_scan(
                query_p, store, 0, ext, cr2, L=L, k=k, interpret=interpret,
                name="bucket_search_pallas_fallback")
        return td[:R], tg[:R], tc[:R], tp

    topd, topg, cnt, passes = jax.lax.cond(overflow, run_full, run_csr,
                                           None)
    R_p = query_p.q.shape[0]
    csr_steps = _grid_steps(E, G * TILE_N) + (
        _grid_steps(R_p, ext - ns) if ext > ns else 0)
    steps = jnp.where(overflow, _grid_steps(R_p, ext), csr_steps)
    return topd, topg, cnt, _counters(guard, cnt, topg, steps, passes)


def bucket_search(*, query: QueryBatch, store: StoreView, cr2, L: int,
                  k: int = 1, use_kernel: bool = True,
                  window_tiles: int = DEFAULT_WINDOW_TILES):
    """Streaming masked top-K NN scan over one shard's store.

    Keyword-only typed surface: ``query`` bundles the R received rows
    (q, qsq, packed probe buckets, probe mask, table), ``store`` bundles
    the N stored rows plus the optional CSR layout.  Returns
    (topd (R, k), topg (R, k), cnt (R,), counters) with the first three
    in (dist^2, gid) lex order, sentinel-padded with (F32_MAX, IMAX) past
    the available hits, and ``counters`` an int32 vector holding, in the
    order of ``SCAN_COUNTERS``, what the CSR path's overflow guard saw:
    whether it fell back to the full scan, the most aligned store tiles a
    row tile's spans needed, the window G it had, the expanded probe rows
    with a non-empty span and all expanded rows (padding included), each
    zero on every other path; then, on every path, the hits within cr
    (the sum of ``cnt``), the top-K ranks that hold a gid (summed over
    rows), the (row tile, store tile) grid steps the Pallas calls of
    the path taken ran and the insertion passes their top-K merge ran,
    summed over those grid steps (both 0 for the oracle).

    Dispatch: ``use_kernel`` and the store's layout alone.  A
    bucket-sorted store (``store.n_sorted > 0``) uses the CSR
    bucket-gather kernel over the sorted region plus a full scan of the
    insert tail -- bitwise identical to the full scan, touching only each
    probe's own bucket rows; an unsorted store (``n_sorted == 0``) takes
    the full-scan kernel.  ``use_kernel=False`` runs the pure jnp oracle
    (always a full scan -- it is the ground truth the kernels are tested
    against, and the XLA path for sharded lowering).
    ``window_tiles`` sizes the gather window (see bucket_gather_pallas);
    oversized spans trigger the traced full-scan fallback, so the value
    only affects performance, never results.  The kernels' full scans
    visit rows [0, ``store.extent``) only (and the tail's [n_sorted,
    extent)); the oracle reads every row.
    """
    if not use_kernel:
        topd, topg, cnt = ref.bucket_search_ref(query=query, store=store,
                                                cr2=cr2, L=L, K=k)
        return topd, topg, cnt, _counters(_no_guard(), cnt, topg, 0, 0)
    R = query.q.shape[0]
    interpret = _on_cpu()
    query_p = _pad_query(query)
    if store.n_sorted > 0:
        return _csr_search(query, query_p, store, cr2, L=L, k=k,
                           window_tiles=window_tiles, interpret=interpret)
    ext = _extent(store)
    topd, topg, cnt, passes = _full_scan(query_p, store, 0, ext, cr2, L=L,
                                         k=k, interpret=interpret)
    topd, topg, cnt = topd[:R], topg[:R], cnt[:R]
    return topd, topg, cnt, _counters(
        _no_guard(), cnt, topg, _grid_steps(query_p.q.shape[0], ext),
        passes)


def flash_attention(q, k, v, *, causal: bool = True, scale=None,
                    use_kernel: bool = True):
    """(B,H,Sq,dh) x (B,Hkv,Sk,dh) -> (B,H,Sq,dh)."""
    if not use_kernel:
        return ref.attention_ref(q, k, v, causal=causal, scale=scale)
    B, H, Sq, dh = q.shape
    Sk = k.shape[2]
    qp = _pad_to(q, 2, TILE_Q)
    kp = _pad_to(k, 2, TILE_K)
    vp = _pad_to(v, 2, TILE_K)
    # causal mask handles padded q rows; seq_k mask handles padded kv
    out = flash_attention_pallas(qp, kp, vp, causal=causal, scale=scale,
                                 seq_k=Sk, interpret=_on_cpu())
    return out[:, :, :Sq, :]


def ssd_scan(x, a_log, b, c, dt, *, use_kernel: bool = True):
    """Mamba-2 SSD scan; broadcasts B/C groups to heads for the kernel."""
    if not use_kernel:
        return ref.ssd_scan_ref(x, a_log, b, c, dt)
    B, S, H, P = x.shape
    G = b.shape[2]
    rep = H // G
    bq = jnp.repeat(b, rep, axis=2)
    cq = jnp.repeat(c, rep, axis=2)
    xp = _pad_to(x, 1, CHUNK)
    bp = _pad_to(bq, 1, CHUNK)
    cp = _pad_to(cq, 1, CHUNK)
    dtp = _pad_to(dt, 1, CHUNK)              # dt=0 -> identity steps
    out = ssd_scan_pallas(xp, a_log, bp, cp, dtp, interpret=_on_cpu())
    return out[:, :S]
