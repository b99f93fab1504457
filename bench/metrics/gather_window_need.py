"""Median, over the window's query-scan steps on the CSR path, of the
aligned store tiles the most demanding row tile's spans needed over the
gather window G the step had (``BatchRecord.need_tiles / window_tiles``);
above 1 the step fell back to the full scan."""
import sys

import numpy as np

from bench.metrics._batches import records


def read(run):
    recs = [r for r in records(run) if r.window_tiles]
    if not recs:
        return None
    print(f"gather_window_need: need tiles per step "
          f"{[r.need_tiles for r in recs]}, window G "
          f"{sorted({r.window_tiles for r in recs})}", file=sys.stderr)
    return float(np.median([r.need_tiles / r.window_tiles for r in recs]))
