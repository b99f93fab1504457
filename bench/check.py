"""The comparison that decides ``correct``, and its limits.

What the window served is held against the plain reference over the
live set (``bench/reference``).  Three numbers:

  rank_dist_gap  -- the widest gap, over every compared query and rank
                    k < K, between the reference's k-th distance and
                    (a) the distance served at rank k and (b) the true
                    distance of the gid served at rank k.  A rank that
                    one side fills and the other leaves empty reads inf.
                    It catches a wrong, missing, extra or altered answer
                    and arithmetic below the stated precision.
  deleted_served -- served gids deleted before the query was admitted
                    (the guarantee: no deleted gid is served).  Exact: 0.
  unanswered     -- queries admitted in the window that failed or never
                    got an answer.  Exact: 0.

Each configuration states its limit on ``rank_dist_gap`` (its distances,
and so its rounding, have their own scale) under ``limits``; PERF.md
gives the readings of sound runs and of the control that each was set
from.  The two counts are exact.
"""
from __future__ import annotations

import numpy as np

IMAX = int(np.iinfo(np.int32).max)

EXACT = {"deleted_served": 0, "unanswered": 0}


def rank_dist_gap(served_d, served_g, true_d, ref_d, ref_g) -> float:
    """Widest per-rank distance gap (see module docstring); arrays (m, K)."""
    s_has = served_g != IMAX
    r_has = ref_g != IMAX
    both = s_has & r_has
    gap = np.where(both, np.maximum(
        np.abs(np.where(both, served_d - ref_d, 0.0)),
        np.abs(np.where(both, true_d - ref_d, 0.0))), 0.0)
    gap = np.where(s_has != r_has, np.inf, gap)
    return float(np.max(gap, initial=0.0))


def compare(served_d, served_g, true_d, ref_d, ref_g, deleted_hits: int,
            unanswered: int, limits: dict) -> dict:
    """Each compared number beside its limit, and the verdict."""
    values = {
        "rank_dist_gap": rank_dist_gap(served_d, served_g, true_d, ref_d,
                                       ref_g),
        "deleted_served": int(deleted_hits),
        "unanswered": int(unanswered),
    }
    lim = {**limits, **EXACT}
    checks = {k: {"value": v, "limit": lim[k]} for k, v in values.items()}
    ok = all(v["value"] <= v["limit"] for v in checks.values())
    return {"correct": ok, "checks": checks}
