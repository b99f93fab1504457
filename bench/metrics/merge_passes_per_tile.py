"""Insertion passes the scan kernels' top-K merge ran per grid step, as
the program counted them: the steady-state batches' (``_batches.steady``)
``BatchRecord.merge_passes`` over their ``grid_steps``, each summed over
those batches.  A tile runs one pass per pair of its busiest row that
enters that row's running top K (at most K, none on a tile that improves
no row), so beside ``scan_us_per_tile`` it tells the merge's share of a
grid step.  It reads the window that ``scan_us_per_tile`` reads, one
whose device trace holds a ``scan_shard`` program for each batch, so the
two divide by the same grid steps.

A program that does not count merge passes, a record kept from one (its
counters stop short of the column), batches that ran no grid step (the
jnp oracle), and a window without such a trace read nothing.
"""
import sys

import numpy as np

from bench import trace
from bench.metrics._batches import records, steady


def _counted(rec) -> bool:
    """Whether the record's program counted merge passes."""
    from repro.kernels.ops import SCAN_COUNTERS
    return ("merge_passes" in SCAN_COUNTERS and np.shape(rec.counters)[-1]
            > SCAN_COUNTERS.index("merge_passes"))


def read(run):
    recs = records(run)
    if not run.events or not recs or not all(_counted(r) for r in recs):
        return None
    w0, w1 = trace.window(run.events)
    if len(trace.modules(run.events, "scan_shard", w0, w1)) != len(recs):
        return None
    keep = {r.seq for r in steady(run)}
    recs = [r for r in recs if r.seq in keep]
    steps = sum(r.grid_steps for r in recs)
    if not steps:
        return None
    passes = sum(r.merge_passes for r in recs)
    print(f"merge_passes_per_tile: {passes} passes over {steps} grid steps "
          f"of {len(recs)} steady steps", file=sys.stderr)
    return passes / steps
