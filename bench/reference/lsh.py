"""Plain reference of the served answer: exact LSH top-K within cr.

For each query, the K live points with the smallest squared distance, in
(distance^2, gid) order, among the points that share a first-layer
bucket H_t(x) = floor((x . A_t + b_t) / W) with one of the query's L
entropy offsets q + r * u_i (u_i uniform on the unit sphere, drawn from
the offset key folded with the query's id) in some table t, and that lie
within distance c * r of the query.  The placement scheme, the shards and
the store layout do not change this set.

It is written from the paper's definitions and derives the hash
parameters and offset keys itself from the configuration's hash seed,
with the same random streams the deployment's definition uses (table 0
from the seed's key, table t from ``fold_in(key, t)``).  It imports
nothing of the system under test and takes nothing it made.  It runs on
the device in blocks of queries and chunks of points, so a 1M-point live
set fits beside nothing else.

``precision`` selects the arithmetic of the distance products and
``hash_precision`` (by default the same) that of the hashing products:
"highest" is float32 throughout, as the deployment states; "high" is
three bfloat16 passes (hi*hi + hi*lo + lo*hi), the next lower precision,
written out so that it means the same on any backend.  "high" is the
benchmark's control (``bench/control.py``).  The reference itself
(``exact``) reports the distances of the K points it chose in float64,
from coordinate differences, so that its own rounding adds nothing to
the gap it reads.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

IMAX = int(np.iinfo(np.int32).max)
HIGHEST = jax.lax.Precision.HIGHEST


def matmul(a, b, precision: str):
    """a @ b in the named precision ("highest" or "high")."""
    if precision == "highest":
        return jnp.matmul(a, b, precision=HIGHEST)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")

    def split(x):
        hi = x.astype(jnp.bfloat16).astype(jnp.float32)
        return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)
    (ah, al), (bh, bl) = split(a), split(b)
    # products of bfloat16 values are exact in float32
    dot = functools.partial(jnp.matmul, precision=HIGHEST)
    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


@dataclasses.dataclass(frozen=True)
class LSHSpec:
    """The index parameters a configuration states."""
    d: int
    k: int
    W: float
    r: float
    c: float
    L: int
    n_tables: int
    hash_seed: int
    K: int

    @classmethod
    def from_config(cls, cfg: dict) -> "LSHSpec":
        ix = cfg["index"]
        return cls(d=int(cfg["dataset"]["dim"]), k=int(ix["k"]),
                   W=float(ix["W"]), r=float(ix["r"]), c=float(ix["c"]),
                   L=int(ix["L"]), n_tables=int(ix["n_tables"]),
                   hash_seed=int(ix["hash_seed"]), K=int(ix["K"]))

    @property
    def cr2(self) -> float:
        return float(np.float32((self.c * self.r) ** 2))


def hash_params(spec: LSHSpec):
    """(A (T, d, k), b (T, k), offset keys (T, 2)) from the hash seed."""
    kp, kq = jax.random.split(jax.random.PRNGKey(spec.hash_seed))
    A, b, okeys = [], [], []
    for t in range(spec.n_tables):
        kt = kp if t == 0 else jax.random.fold_in(kp, t)
        kA, kb = jax.random.split(kt, 7)[:2]
        A.append(jax.random.normal(kA, (spec.d, spec.k), jnp.float32))
        b.append(jax.random.uniform(kb, (spec.k,), jnp.float32,
                                    maxval=spec.W))
        okeys.append(kq if t == 0 else jax.random.fold_in(kq, t))
    return jnp.stack(A), jnp.stack(b), jnp.stack(okeys)


def buckets(A, b, x, W: float, precision: str):
    """(T, n, k) int32 first-layer buckets of points x (n, d)."""
    return jax.vmap(lambda a, c: jnp.floor(
        (matmul(x, a, precision) + c) / jnp.float32(W)).astype(jnp.int32))(
            A, b)


def offsets(okey, qids, q, L: int, r: float):
    """(m, L, d) entropy offsets of queries q (m, d) with ids qids (m,)."""
    def one(i, v):
        g = jax.random.normal(jax.random.fold_in(okey, i), (L, v.shape[0]),
                              jnp.float32)
        u = g / jnp.maximum(jnp.linalg.norm(g, axis=-1, keepdims=True),
                            1e-12)
        return v[None, :] + jnp.float32(r) * u
    return jax.vmap(one)(qids, q)


def probe_buckets(spec: LSHSpec, A, b, okeys, qids, q, precision: str):
    """(T, m, L, k) buckets of every query's offsets in every table."""
    m = q.shape[0]
    out = []
    for t in range(spec.n_tables):
        offs = offsets(okeys[t], qids, q, spec.L, spec.r)
        hk = buckets(A[t:t + 1], b[t:t + 1],
                     offs.reshape(m * spec.L, spec.d), spec.W, precision)
        out.append(hk[0].reshape(m, spec.L, spec.k))
    return jnp.stack(out)


def sq_dists(q, x, precision: str):
    """(m, n) squared distances, clamped at 0."""
    d2 = (jnp.sum(q * q, -1)[:, None] + jnp.sum(x * x, -1)[None, :]
          - 2.0 * matmul(q, x.T, precision))
    return jnp.maximum(d2, 0.0)


@functools.partial(jax.jit, static_argnames=("spec", "precision"))
def _topk_block(q, probes, xs, hks, lives, *, spec: LSHSpec, precision):
    """Exact top-K of one query block over all point chunks (a scan)."""
    m, K = q.shape[0], spec.K
    B = xs.shape[1]

    def chunk(carry, inp):
        best_d, best_g = carry
        x, hk, live, c = inp
        cand = jnp.zeros((m, B), bool)
        for t in range(spec.n_tables):
            for l_ in range(spec.L):
                cand = cand | jnp.all(
                    probes[t, :, l_, None, :] == hk[t, None, :, :], axis=-1)
        d2 = sq_dists(q, x, precision)
        ok = cand & live[None, :] & (d2 <= jnp.float32(spec.cr2))
        # top_k puts the lower index first among equal values, and rows of
        # a chunk are in gid order: the chunk's best K in (d2, gid) order
        val, idx = jax.lax.top_k(jnp.where(ok, -d2, -jnp.inf), K)
        got = val > -jnp.inf
        cd = jnp.where(got, -val, jnp.inf)
        cg = jnp.where(got, c * B + idx, IMAX).astype(jnp.int32)
        sd, sg = jax.lax.sort((jnp.concatenate([best_d, cd], 1),
                               jnp.concatenate([best_g, cg], 1)),
                              dimension=1, num_keys=2)
        return (sd[:, :K], sg[:, :K]), None

    init = (jnp.full((m, K), jnp.inf, jnp.float32),
            jnp.full((m, K), IMAX, jnp.int32))
    n_chunks = xs.shape[0]
    (best_d, best_g), _ = jax.lax.scan(
        chunk, init, (xs, hks, lives, jnp.arange(n_chunks, dtype=jnp.int32)))
    return best_d, best_g


class Reference:
    """The reference over one set of rows (gid = row index)."""

    def __init__(self, spec: LSHSpec, rows: np.ndarray,
                 precision: str = "highest",
                 hash_precision: Optional[str] = None, exact: bool = True,
                 chunk: int = 32768, block: int = 128):
        self.spec = spec
        self.exact = exact
        self.rows = rows
        self.precision = precision
        self.hash_precision = hash_precision or precision
        self.block = block
        n = rows.shape[0]
        self.chunk = min(chunk, -(-n // 8) * 8)
        n_pad = -(-n // self.chunk) * self.chunk
        self.n = n
        self.A, self.b, self.okeys = hash_params(spec)
        x = np.zeros((n_pad, spec.d), np.float32)
        x[:n] = rows
        self.x = jnp.asarray(x)
        hk = jnp.concatenate([
            buckets(self.A, self.b, self.x[s:s + self.chunk], spec.W,
                    self.hash_precision)
            for s in range(0, n_pad, self.chunk)], axis=1)
        nc = n_pad // self.chunk
        self.xs = self.x.reshape(nc, self.chunk, spec.d)
        self.hks = jnp.moveaxis(
            hk.reshape(spec.n_tables, nc, self.chunk, spec.k), 1, 0)

    def topk(self, queries: np.ndarray, qids: np.ndarray,
             live: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(m, K) distances (inf pad) and gids (IMAX pad) of queries
        (m, d) with ids qids over the rows where ``live`` (n,) holds.

        Candidates are chosen in the reference's precision.  With
        ``exact`` their distances are then those of ``dists`` and the K
        re-sorted by them; otherwise they are as that precision gives."""
        m = queries.shape[0]
        lv = np.zeros((self.xs.shape[0] * self.chunk,), bool)
        lv[:self.n] = live
        lives = jnp.asarray(lv.reshape(self.xs.shape[0], self.chunk))
        out_d = np.zeros((m, self.spec.K), np.float64)
        out_g = np.zeros((m, self.spec.K), np.int32)
        for s in range(0, m, self.block):
            e = min(m, s + self.block)
            q = np.zeros((self.block, self.spec.d), np.float32)
            i = np.zeros((self.block,), np.int32)
            q[:e - s], i[:e - s] = queries[s:e], qids[s:e]
            q, i = jnp.asarray(q), jnp.asarray(i)
            probes = probe_buckets(self.spec, self.A, self.b, self.okeys, i,
                                   q, self.hash_precision)
            d2, g = _topk_block(q, probes, self.xs, self.hks, lives,
                                spec=self.spec, precision=self.precision)
            out_d[s:e] = np.sqrt(np.asarray(d2)[:e - s])
            out_g[s:e] = np.asarray(g)[:e - s]
        if self.exact:
            out_d = self.dists(queries, out_g)
            order = np.lexsort((out_g, out_d), axis=-1)
            out_d = np.take_along_axis(out_d, order, -1)
            out_g = np.take_along_axis(out_g, order, -1)
        return out_d, out_g

    def dists(self, queries: np.ndarray, gids: np.ndarray) -> np.ndarray:
        """(m, K) distances from each query to the rows it was served,
        summed in float64 over coordinate differences (inf where the gid
        is padding or names no row)."""
        ok = (gids >= 0) & (gids < self.n)
        x = self.rows[np.where(ok, gids, 0)].astype(np.float64)  # (m, K, d)
        diff = x - queries.astype(np.float64)[:, None, :]
        return np.where(ok, np.sqrt(np.sum(diff * diff, -1)), np.inf)
