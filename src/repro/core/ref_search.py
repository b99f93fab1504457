"""Brute-force reference search: exact NN / top-K ground truth for tests
and recall@K measurement on small datasets."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

IMAX = np.iinfo(np.int32).max


def sq_dists(queries: jax.Array, points: jax.Array) -> jax.Array:
    """(m, n) squared distances at full f32 precision on every device."""
    d2 = (jnp.sum(queries ** 2, -1)[:, None]
          + jnp.sum(points ** 2, -1)[None, :]
          - 2.0 * jnp.matmul(queries, points.T,
                             precision=jax.lax.Precision.HIGHEST))
    return jnp.maximum(d2, 0.0)


@jax.jit
def _chunk_min(queries: jax.Array, chunk: jax.Array):
    d2 = sq_dists(queries, chunk)
    return jnp.min(d2, axis=1), jnp.argmin(d2, axis=1)


def nearest_neighbor(data: np.ndarray, queries: np.ndarray,
                     chunk: int = 8192) -> tuple[np.ndarray, np.ndarray]:
    """Exact NN: returns (dist, idx) arrays of shape (m,)."""
    m = queries.shape[0]
    best = np.full((m,), np.inf, np.float32)
    arg = np.zeros((m,), np.int64)
    q = jnp.asarray(queries, jnp.float32)
    for s in range(0, data.shape[0], chunk):
        e = min(data.shape[0], s + chunk)
        d2, a = _chunk_min(q, jnp.asarray(data[s:e], jnp.float32))
        d2, a = np.asarray(d2), np.asarray(a)
        upd = d2 < best
        best = np.where(upd, d2, best)
        arg = np.where(upd, a + s, arg)
    return np.sqrt(best), arg


def topk_sort_jnp(d: jax.Array, g: jax.Array, k: int,
                  pad_d=jnp.inf) -> tuple[jax.Array, jax.Array]:
    """(m, c) masked (dist, id) pairs -> the k best per row in (dist, id)
    lex order, sentinel-padded (pad_d, IMAX) when c < k.  The one sort
    whose tie-break semantics every top-K path (kernel oracle, jnp query
    path, simulators) must share."""
    if d.shape[1] < k:
        padw = ((0, 0), (0, k - d.shape[1]))
        d = jnp.pad(d, padw, constant_values=pad_d)
        g = jnp.pad(g, padw, constant_values=IMAX)
    sd, sg = jax.lax.sort((d, g), dimension=1, num_keys=2)
    return sd[:, :k], sg[:, :k]


def topk_merge_host(best: np.ndarray, arg: np.ndarray,
                    cand_d, cand_g) -> tuple[np.ndarray, np.ndarray]:
    """Merge a running host-side (m, k) top-K with (m, c) new candidates,
    preserving (dist, id) lex order (chunked-scan accumulator step)."""
    k = best.shape[1]
    cd = np.concatenate([best, np.asarray(cand_d)], axis=1)
    cg = np.concatenate([arg, np.asarray(cand_g)], axis=1)
    order = np.lexsort((cg, cd), axis=1)[:, :k]
    return (np.take_along_axis(cd, order, axis=1),
            np.take_along_axis(cg, order, axis=1))


@functools.partial(jax.jit, static_argnames=("k",))
def _chunk_topk(queries: jax.Array, chunk: jax.Array, idx0: int, *, k: int):
    d2 = sq_dists(queries, chunk)
    idx = jnp.broadcast_to(
        idx0 + jnp.arange(chunk.shape[0], dtype=jnp.int32)[None, :],
        d2.shape)
    return topk_sort_jnp(d2, idx, k)


def nearest_neighbors(data: np.ndarray, queries: np.ndarray, k: int,
                      chunk: int = 8192) -> tuple[np.ndarray, np.ndarray]:
    """Exact top-K NN in (dist, idx) lex order: (m, k) dist and idx arrays
    (inf / IMAX padded when the dataset has fewer than k points) -- the
    recall@K ground truth of the survey's evaluation methodology."""
    m = queries.shape[0]
    best = np.full((m, k), np.inf, np.float32)
    arg = np.full((m, k), IMAX, np.int32)
    q = jnp.asarray(queries, jnp.float32)
    for s in range(0, data.shape[0], chunk):
        e = min(data.shape[0], s + chunk)
        d2, ci = _chunk_topk(q, jnp.asarray(data[s:e], jnp.float32),
                             np.int32(s), k=k)
        best, arg = topk_merge_host(best, arg, d2, ci)
    return np.sqrt(best), arg
