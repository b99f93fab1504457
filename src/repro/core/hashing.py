"""LSH hash families (Datar et al. p-stable construction) and the paper's
second-layer Gaussian LSH ``G``.

First layer:   H(v)   = (h_1(v) .. h_k(v)),  h_i(v) = floor((a_i.v + b_i)/W)
Pre-floor map: Gamma_i(v) = (a_i.v + b_i)/W            (Lemma 4 uses this)
Second layer:  G(u)   = floor((alpha.u + beta)/D),  u in R^k  (eq. 3.1)
Cauchy layer:  same as G but alpha ~ standard Cauchy (Haghani et al.)

Bucket identity Z^k -> compact key: two independent 32-bit universal hashes
(uint32 wrap-around arithmetic), so equality of packed ids equals equality
of bucket vectors up to a 2^-64 collision chance.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.config import LSHConfig, Scheme

# Hashes are a function of the data, not of the device: every projection
# runs at full f32 precision (the TPU default would round the operands
# to bf16 and move points across bucket boundaries).
HIGHEST = jax.lax.Precision.HIGHEST


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class HashParams:
    """Sampled parameters for one hash table (one H in H'_W plus one G)."""

    A: jax.Array          # (d, k) float32, N(0,1) entries
    b: jax.Array          # (k,)   float32, U[0, W)
    alpha: jax.Array      # (k,)   float32, N(0,1)   -- layered G
    beta: jax.Array       # ()     float32, U[0, D)
    alpha_cauchy: jax.Array  # (k,) float32, standard Cauchy -- baseline
    pack_mult: jax.Array  # (k, 2) uint32 odd multipliers for bucket packing
    pack_add: jax.Array   # (2,)   uint32

    def tree_flatten(self):
        return (
            (self.A, self.b, self.alpha, self.beta, self.alpha_cauchy,
             self.pack_mult, self.pack_add),
            None,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class StackedHashParams:
    """All T tables' ``HashParams`` stacked on a leading table axis.

    This is the index's CANONICAL parameter form: every field carries a
    leading ``(T, ...)`` axis, so the hot paths hash under all tables with
    ONE vmapped call (params broadcast over the T axis) instead of a
    Python loop, and the receive side gathers ``params[table_id]`` per
    routed row and hashes once -- O(L*k*d) per row instead of O(T*L*k*d),
    with compiled trace size independent of T.

    Stacking preserves each table's values bit-for-bit (``jnp.stack`` of
    the per-table samples), and the vmapped/gathered matmuls contract over
    d in the same order as the unstacked path, so table 0 of a stack
    reproduces the single-table hash stream bitwise (tested).
    """

    A: jax.Array          # (T, d, k)
    b: jax.Array          # (T, k)
    alpha: jax.Array      # (T, k)
    beta: jax.Array       # (T,)
    alpha_cauchy: jax.Array  # (T, k)
    pack_mult: jax.Array  # (T, k, 2)
    pack_add: jax.Array   # (T, 2)

    def tree_flatten(self):
        return (
            (self.A, self.b, self.alpha, self.beta, self.alpha_cauchy,
             self.pack_mult, self.pack_add),
            None,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def n_tables(self) -> int:
        return self.A.shape[0]

    @classmethod
    def stack(cls, tables: list[HashParams]) -> "StackedHashParams":
        """Stack per-table ``HashParams`` (bit-preserving)."""
        if not tables:
            raise ValueError("need at least one table")
        return cls(*(jnp.stack([getattr(p, f.name) for p in tables])
                     for f in dataclasses.fields(HashParams)))

    def table(self, t: int) -> HashParams:
        """Per-table compat view (table t's parameters, unstacked)."""
        return HashParams(self.A[t], self.b[t], self.alpha[t], self.beta[t],
                          self.alpha_cauchy[t], self.pack_mult[t],
                          self.pack_add[t])

    def as_tables(self) -> list[HashParams]:
        return [self.table(t) for t in range(self.n_tables)]

    def gather(self, tables: jax.Array) -> HashParams:
        """Per-row parameter gather: ``tables`` (R,) int32 table ids ->
        a ``HashParams`` pytree whose every field carries a leading R
        axis (row i holds table ``tables[i]``'s parameters), ready for a
        row-wise ``jax.vmap`` of the hash functions."""
        return HashParams(self.A[tables], self.b[tables],
                          self.alpha[tables], self.beta[tables],
                          self.alpha_cauchy[tables],
                          self.pack_mult[tables], self.pack_add[tables])


def table_key(key: jax.Array, table: int) -> jax.Array:
    """RNG key for one table of a multi-table config.

    Table 0 uses ``key`` itself, so a T-table index reproduces the
    single-table parameter stream bit-for-bit in its first table, and the
    table sequence is a nested prefix (raising T never resamples the
    existing tables).
    """
    return key if table == 0 else jax.random.fold_in(key, table)


def sample_params(key: jax.Array, cfg: LSHConfig) -> HashParams:
    kA, kb, ka, kB, kc, km, kp = jax.random.split(key, 7)
    A = jax.random.normal(kA, (cfg.d, cfg.k), dtype=jnp.float32)
    b = jax.random.uniform(kb, (cfg.k,), dtype=jnp.float32, maxval=cfg.W)
    alpha = jax.random.normal(ka, (cfg.k,), dtype=jnp.float32)
    beta = jax.random.uniform(kB, (), dtype=jnp.float32, maxval=float(cfg.D))
    # Standard Cauchy via inverse-CDF of U(0,1).
    u = jax.random.uniform(kc, (cfg.k,), dtype=jnp.float32,
                           minval=1e-6, maxval=1.0 - 1e-6)
    alpha_cauchy = jnp.tan(jnp.pi * (u - 0.5))
    pack_mult = (
        jax.random.randint(km, (cfg.k, 2), 0, jnp.iinfo(jnp.int32).max,
                           dtype=jnp.int32).astype(jnp.uint32) * 2 + 1
    )
    pack_add = jax.random.randint(kp, (2,), 0, jnp.iinfo(jnp.int32).max,
                                  dtype=jnp.int32).astype(jnp.uint32)
    return HashParams(A, b, alpha, beta, alpha_cauchy, pack_mult, pack_add)


def sample_table_params(key: jax.Array, cfg: LSHConfig) -> list[HashParams]:
    """One independent ``HashParams`` per fused table (length n_tables).

    Entry 0 equals ``sample_params(key, cfg)`` exactly; entry t draws from
    ``table_key(key, t)``.  Each table also gets its own bucket-packing
    multipliers, so packed ids from different tables collide only with
    the generic 2^-64 chance -- the explicit table mask in the search
    path removes even that.
    """
    return [sample_params(table_key(key, t), cfg)
            for t in range(cfg.n_tables)]


def sample_stacked_params(key: jax.Array, cfg: LSHConfig) -> StackedHashParams:
    """The canonical stacked form of ``sample_table_params`` (same values,
    leading T axis on every field)."""
    return StackedHashParams.stack(sample_table_params(key, cfg))


# ---------------------------------------------------------------------------
# First layer H and its pre-floor map Gamma
# ---------------------------------------------------------------------------

def gamma(params: HashParams, x: jax.Array, W: float) -> jax.Array:
    """Gamma(x) = (A^T x + b) / W  with shape (..., k)."""
    return (jnp.matmul(x.astype(jnp.float32), params.A, precision=HIGHEST)
            + params.b) / jnp.float32(W)


def hash_h(params: HashParams, x: jax.Array, W: float) -> jax.Array:
    """H(x) = floor(Gamma(x)) as int32, shape (..., k)."""
    return jnp.floor(gamma(params, x, W)).astype(jnp.int32)


def pack_buckets(params: HashParams, hk: jax.Array) -> jax.Array:
    """Pack integer bucket vectors (..., k) into (..., 2) uint32 keys."""
    hu = hk.astype(jnp.uint32)
    packed = (hu[..., :, None] * params.pack_mult).sum(axis=-2)
    return packed + params.pack_add  # (..., 2) uint32, wrap-around


# ---------------------------------------------------------------------------
# Second layer G (the paper's eq. 3.1) and baselines
# ---------------------------------------------------------------------------

def g_of(params: HashParams, hk: jax.Array, D: float) -> jax.Array:
    """G(u) = floor((alpha.u + beta)/D) applied to bucket vectors (..., k)."""
    proj = jnp.matmul(hk.astype(jnp.float32), params.alpha,
                      precision=HIGHEST) + params.beta
    return jnp.floor(proj / jnp.float32(D)).astype(jnp.int32)


def g_cauchy_of(params: HashParams, hk: jax.Array, D: float) -> jax.Array:
    proj = jnp.matmul(hk.astype(jnp.float32), params.alpha_cauchy,
                      precision=HIGHEST) + params.beta
    return jnp.floor(proj / jnp.float32(D)).astype(jnp.int32)


def g_sum_of(hk: jax.Array) -> jax.Array:
    """Haghani et al. 'Sum': the sum of bucket coordinates."""
    return hk.sum(axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Scheme dispatch: bucket vector (..., k) -> shard key (int32) and shard id
# ---------------------------------------------------------------------------

def shard_key(params: HashParams, cfg: LSHConfig, hk: jax.Array) -> jax.Array:
    """The integer Key whose value determines the machine (paper sec. 3).

    For SIMPLE this is a uniform 32-bit hash of the bucket id; for the
    others it is the (locality-sensitive) re-hash of the bucket vector.
    """
    if cfg.scheme == Scheme.SIMPLE:
        return pack_buckets(params, hk)[..., 0].astype(jnp.int32)
    if cfg.scheme == Scheme.LAYERED:
        return g_of(params, hk, float(cfg.D))
    if cfg.scheme == Scheme.SUM:
        return g_sum_of(hk)
    if cfg.scheme == Scheme.CAUCHY:
        return g_cauchy_of(params, hk, float(cfg.D))
    raise ValueError(f"unknown scheme {cfg.scheme}")


def shard_of(params: HashParams, cfg: LSHConfig, hk: jax.Array) -> jax.Array:
    """Machine id in [0, n_shards) for a bucket vector (..., k).

    The paper assumes Key -> machine is the identity; on a finite mesh we
    take the Key mod n_shards (uniform for SIMPLE, locality-preserving
    blocks for the LSH-based schemes).
    """
    key = shard_key(params, cfg, hk)
    return jnp.mod(key, jnp.int32(cfg.n_shards)).astype(jnp.int32)


def gh(params: HashParams, cfg: LSHConfig, x: jax.Array) -> jax.Array:
    """GH(x) for points x (..., d) -> int32 Keys (scheme-dependent)."""
    return shard_key(params, cfg, hash_h(params, x, cfg.W))
