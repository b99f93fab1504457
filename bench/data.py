"""Datasets of the benchmark's deployments, generated on the device.

A configuration names its dataset by ``kind`` with the sizes and a fixed
``seed``: the dataset is part of the deployment (the paper's Random data
set is one set of points), so every run of a cell indexes the same rows
and only the traffic differs from seed to seed.  Rows are made in blocks
of ``BLOCK``, each block from its own ``fold_in`` of the seed's key, so
row ``i`` is the same however many rows a run asks for.

Kinds:
  gaussian  -- coordinates N(0, 1/d): points ~1 from the origin, ~sqrt(2)
               apart (the paper's "Random" data).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 4096
_BLOCKS_PER_CALL = 64


@functools.partial(jax.jit, static_argnames=("nb", "d"))
def _blocks(key, first, *, nb, d):
    """Rows of blocks first .. first + nb - 1, shaped (nb, BLOCK, d)."""
    idx = first + jnp.arange(nb)
    noise = jax.vmap(lambda j: jax.random.normal(
        jax.random.fold_in(key, j), (BLOCK, d), jnp.float32))(idx)
    return noise * jnp.float32(1.0 / np.sqrt(d))


class Dataset:
    """Rows of one configuration's dataset, kept on the host once made."""

    def __init__(self, spec: dict):
        self.kind = spec["kind"]
        if self.kind != "gaussian":
            raise ValueError(f"unknown dataset kind {self.kind!r}")
        self.d = int(spec["dim"])
        self.seed = int(spec["seed"])
        self._rows = np.zeros((0, self.d), np.float32)

    def rows(self, hi: int) -> np.ndarray:
        """The first ``hi`` rows (made on the device as needed)."""
        if hi > len(self._rows):
            have = len(self._rows) // BLOCK
            key = jax.random.PRNGKey(self.seed)
            parts = [self._rows[:have * BLOCK]]
            for first in range(have, -(-hi // BLOCK), _BLOCKS_PER_CALL):
                out = _blocks(key, first, nb=_BLOCKS_PER_CALL, d=self.d)
                parts.append(np.asarray(out).reshape(-1, self.d))
            self._rows = np.concatenate(parts)
        return self._rows[:hi]
