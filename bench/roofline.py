"""The scan's work, counted from what it must read and compute.

The work of answering one batch is the same whatever implements the scan:
every live row of each distinct bucket that the batch's probes name is
read once (its vector and gid, ``4 * d + 4`` bytes), and every (query,
candidate row) pair costs one ``2 * d``-flop distance.  A candidate row of
a query is a live row in one of the query's distinct probed buckets.

Buckets are compared as whole (table, k-vector) keys, hashed by the
reference (``bench/reference``), against a histogram of the live rows.
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip; an unknown kind is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table[device_kind]


def _keys(buckets: np.ndarray) -> np.ndarray:
    """(T, ..., k) int32 buckets -> (...)-shaped void keys of (t, vector)."""
    T = buckets.shape[0]
    t = np.broadcast_to(
        np.arange(T, dtype=np.int32).reshape((T,) + (1,) * (buckets.ndim - 1)),
        buckets.shape[:-1] + (1,))
    full = np.ascontiguousarray(np.concatenate([t, buckets], axis=-1))
    return full.view(np.dtype((np.void, full.shape[-1] * 4)))[..., 0]


def scan_work(live_buckets: np.ndarray, probes: list[np.ndarray],
              d: int) -> list[tuple[float, float]]:
    """(flops, bytes) of each batch.

    live_buckets: (T, n_live, k) buckets of the live rows.
    probes: per batch, (T, m, L, k) buckets of its queries' probes.
    """
    uniq, counts = np.unique(_keys(live_buckets).reshape(-1),
                             return_counts=True)
    out = []
    for pb in probes:
        T, m, L, _ = pb.shape
        keys = _keys(pb)                                   # (T, m, L)
        pos = np.searchsorted(uniq, keys)
        pos_c = np.minimum(pos, len(uniq) - 1)
        rows = np.where(uniq[pos_c] == keys, counts[pos_c], 0)
        flops = 0.0
        for q in range(m):
            kq = keys[:, q, :].reshape(-1)
            _, first = np.unique(kq, return_index=True)
            flops += 2.0 * d * rows[:, q, :].reshape(-1)[first].sum()
        _, first = np.unique(keys.reshape(-1), return_index=True)
        nbytes = (4.0 * d + 4.0) * rows.reshape(-1)[first].sum()
        out.append((flops, nbytes))
    return out


def least_seconds(work: list[tuple[float, float]], peak: dict
                  ) -> tuple[float, str]:
    """Least time the chip could take for all of ``work`` (each batch
    bounded by the larger of its flops over the peak rate and its bytes
    over the bandwidth), and which of the two bounds most of it."""
    pf, pb = float(peak["flops_per_s"]), float(peak["hbm_bytes_per_s"])
    t_f = sum(max(f / pf, b / pb) for f, b in work if f / pf >= b / pb)
    t_b = sum(max(f / pf, b / pb) for f, b in work if f / pf < b / pb)
    return t_f + t_b, ("flops" if t_f >= t_b else "bytes")
