"""The one traffic generator: it reads a mix from ``bench/traffic/<mix>.json``.

A mix is data.  Its keys:

  loop         -- "closed": ``outstanding`` buckets of ``bucket`` queries
                  stay in flight; each answered bucket is replaced at once.
  bucket       -- queries per submitted batch (the service's bucket size).
  outstanding  -- batches in flight at once.
  setup        -- writes before the window, after build and compact:
                  ``insert`` new rows into the tail and ``delete`` that many
                  gids, ``delete_from_insert`` of them among the new rows
                  (the rest among the built rows), drawn from the seed.
  near         -- of each bucket's queries, how many are planted near a
                  row that the set-up ``inserted`` (and is live) and near
                  one it ``deleted``; the rest are planted near any live
                  row.  So every bucket asks for rows the set-up wrote.

Every query is planted, as in the paper's Random data set: a row, drawn
from the seed, plus Gaussian noise of norm ~r.  Which rows are inserted
does not depend on the seed, and every bucket has the same mix, so every
seed does the same amount of work in the same store shapes; the seed
picks the queries and the set-up deletes.
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    if mix["loop"] != "closed":
        raise ValueError(f"mix {name}: unknown loop {mix['loop']!r}")
    return mix


class Traffic:
    """Queries and writes of one mix, drawn from the seed."""

    def __init__(self, mix: dict, d: int, r: float, seed: int):
        self.mix = mix
        self.bucket = int(mix["bucket"])
        self.outstanding = int(mix["outstanding"])
        self.setup = mix.get("setup", {})
        self.d, self.r = d, r
        self._rng = np.random.default_rng(seed)
        self._warm_rng = np.random.default_rng([seed, 1])

    def set_live(self, live: np.ndarray, n_built: int) -> None:
        """The rows queries are planted near, once the set-up has written:
        ``live`` (n,) over every row made, the first ``n_built`` built."""
        self._pools = {"live": np.flatnonzero(live),
                       "inserted": n_built + np.flatnonzero(live[n_built:]),
                       "deleted": np.flatnonzero(~live)}

    def plant(self, rows: np.ndarray, warm: bool = False
              ) -> tuple[np.ndarray, np.ndarray]:
        """One bucket of queries (bucket, d) planted near rows, and the
        rows' gids."""
        rng = self._warm_rng if warm else self._rng
        near = {k: int(v) for k, v in self.mix.get("near", {}).items()}
        near["live"] = self.bucket - sum(near.values())
        base = np.concatenate([
            self._pools[k][rng.integers(0, len(self._pools[k]), c)]
            for k, c in sorted(near.items()) if c])
        base = base[rng.permutation(self.bucket)]
        noise = rng.standard_normal((self.bucket, self.d)) * (
            self.r / np.sqrt(self.d))
        return (rows[base] + noise).astype(np.float32), base

    def setup_deletes(self, n_built: int, inserted: np.ndarray) -> np.ndarray:
        """The set-up's delete batch: gids among built and inserted rows."""
        n = int(self.setup.get("delete", 0))
        n_ins = int(self.setup.get("delete_from_insert", 0))
        return np.concatenate([
            self._rng.choice(n_built, n - n_ins, replace=False),
            self._rng.choice(inserted, n_ins, replace=False)])
