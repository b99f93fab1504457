"""Mean device time of the query scan's Pallas kernels per grid step, in
microseconds: each steady-state batch's (``_batches.steady``) kernel time
in its ``scan_shard`` program (the ops named ``bucket_gather_pallas*``
and ``bucket_search_pallas*``, overlaps counted once) over the (row
tile, store tile) grid steps its kernels ran (``BatchRecord.grid_steps``),
averaged over those batches.  Side by side, cells that differ only in K
read the per-tile price of the kernels' top-K merge.

Batches pair with programs by order, as in ``device_queue_ms``: the
window's k-th batch with its k-th ``scan_shard`` program; a window with
a different number of each reads nothing.  A program whose records count
no grid steps (one from before the count, or the jnp oracle) reads
nothing.  It also prints the hits within cr per query and the share of
the answers' ranks filled, ``filled / (queries * K)``, on standard
error (one shard and one table: a query is one scan row).
"""
import sys

from bench import trace
from bench.metrics._batches import records, steady
from bench.metrics.scan_glue_ms import KERNELS


def _skip(why):
    print(f"scan_us_per_tile: {why}; not read", file=sys.stderr)


def kernel_ns(events, step) -> float:
    """Device time of the scan kernels inside one ``scan_shard`` run."""
    return trace.union_ns(
        [(op["start_ns"], op["start_ns"] + op["dur_ns"])
         for op in trace.ops_inside(events, step)
         if trace.op_name(op["name"]).startswith(KERNELS)])


def read(run):
    recs = records(run)
    if not run.events or not recs:
        return None
    if not all(getattr(r, "grid_steps", 0) for r in recs):
        return _skip("a batch's record counts no grid steps")
    w0, w1 = trace.window(run.events)
    steps = sorted(trace.modules(run.events, "scan_shard", w0, w1),
                   key=lambda e: e["start_ns"])
    if len(steps) != len(recs):
        return _skip(f"{len(steps)} scan programs for {len(recs)} batches")
    keep = {r.seq for r in steady(run)}
    per_tile = [kernel_ns(run.events, s) / r.grid_steps / 1e3
                for r, s in zip(recs, steps) if r.seq in keep]
    if not per_tile:
        return None
    queries = sum(r.take for r in recs)
    ref = getattr(run, "reference", None)
    K = ref.spec.K if ref is not None else None
    filled = sum(r.filled for r in recs)
    print(f"scan_us_per_tile: {len(per_tile)} steady steps, "
          f"{sum(r.grid_steps for r in recs) / len(recs):.0f} grid steps "
          f"a batch; hits/query {sum(r.hits for r in recs) / queries:.2f}, "
          f"filled/(queries*K) "
          + (f"{filled / (queries * K):.5f}" if K else "(no K)"),
          file=sys.stderr)
    return sum(per_tile) / len(per_tile)
