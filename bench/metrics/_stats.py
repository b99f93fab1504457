"""Statistics shared by the readers."""
from __future__ import annotations

import math

import numpy as np


def percentile(values, q: float) -> float:
    """Nearest-rank q-th percentile of every value (inf counts as the
    largest): the smallest value with at least q% of the values at or
    below it."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("no values")
    rank = max(1, math.ceil(q / 100.0 * v.size))
    return float(v[rank - 1])
