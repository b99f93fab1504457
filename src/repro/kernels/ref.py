"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each function mirrors its kernel's contract exactly, with no tiling and
no VMEM reasoning -- plain jnp ops only.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32_MAX = jnp.float32(jnp.finfo(jnp.float32).max)
IMAX = jnp.int32(jnp.iinfo(jnp.int32).max)


def lsh_hash_ref(x: jax.Array, a: jax.Array, b: jax.Array, *,
                 w: float) -> jax.Array:
    """floor((x @ a + b) / w) as int32."""
    proj = (x.astype(jnp.float32) @ a.astype(jnp.float32)
            + b.astype(jnp.float32)) / jnp.float32(w)
    return jnp.floor(proj).astype(jnp.int32)


def bucket_search_ref(*, query, store, cr2, L: int, K: int = 1):
    """Masked top-K NN full scan; see bucket_search_pallas for the
    contract.  Takes the same ``QueryBatch``/``StoreView`` dataclasses as
    the kernels (keyword-only); the StoreView's CSR fields are ignored --
    this oracle is the layout-agnostic ground truth that both the full
    scan and the CSR gather must reproduce.

    Returns (topd (R, K), topg (R, K), cnt (R,)): per-row K best
    (dist^2, gid) pairs in (dist^2, gid) lex order, sentinel-padded with
    (F32_MAX, IMAX) when fewer than K points hit.  A stored row only
    matches probes of its own table (multi-table fusion).
    """
    q, p = query.q, store.points
    d2 = (query.qsq[:, None] + store.psq[None, :]
          - 2.0 * jnp.matmul(q, p.T, precision=jax.lax.Precision.HIGHEST))
    d2 = jnp.maximum(d2, 0.0)
    qb = query.buckets.reshape(q.shape[0], L, 2)
    pbuckets, probe, gid = store.buckets, query.probe, store.gid
    match = jnp.any(
        (qb[:, :, 0, None] == pbuckets[None, None, :, 0])
        & (qb[:, :, 1, None] == pbuckets[None, None, :, 1])
        & (probe[:, :, None] > 0), axis=1)
    match = match & (store.valid[None, :] > 0)
    match = match & (query.table[:, None] == store.table[None, :])
    hit = match & (d2 <= cr2)
    d2m = jnp.where(hit, d2, F32_MAX)
    gidm = jnp.where(hit, jnp.broadcast_to(gid[None, :], d2m.shape), IMAX)
    sd, sg = jax.lax.sort((d2m, gidm), dimension=1, num_keys=2)
    pad = max(0, K - sd.shape[1])
    if pad:
        sd = jnp.pad(sd, ((0, 0), (0, pad)), constant_values=F32_MAX)
        sg = jnp.pad(sg, ((0, 0), (0, pad)),
                     constant_values=jnp.iinfo(jnp.int32).max)
    cnt = jnp.sum(hit, axis=1).astype(jnp.int32)
    return sd[:, :K], sg[:, :K], cnt


def attention_ref(q, k, v, *, causal: bool = True,
                  scale: float | None = None) -> jax.Array:
    """Exact softmax attention with GQA broadcast; f32 accumulation."""
    B, H, Sq, dh = q.shape
    _, Hkv, Sk, _ = k.shape
    group = H // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    kq = jnp.repeat(k, group, axis=1).astype(jnp.float32)
    vq = jnp.repeat(v, group, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32) * scale, kq)
    if causal:
        mask = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
        s = jnp.where(mask[None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, vq).astype(q.dtype)


def ssd_scan_ref(x, a_log, b, c, dt, *, chunk: int = 64) -> jax.Array:
    """Mamba-2 SSD (state-space dual) sequential reference.

    Args:
      x:     (B, S, H, P)  inputs per head
      a_log: (H,)          log of -A (positive decay rate per head)
      b:     (B, S, G, N)  input->state projection (G groups broadcast to H)
      c:     (B, S, G, N)  state->output projection
      dt:    (B, S, H)     softplus-activated step sizes
    Returns:
      y: (B, S, H, P)
    """
    B, S, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    rep = H // G
    bq = jnp.repeat(b, rep, axis=2)  # (B, S, H, N)
    cq = jnp.repeat(c, rep, axis=2)
    a = -jnp.exp(a_log)              # (H,)
    decay = jnp.exp(a[None, None, :] * dt)  # (B, S, H)

    def step(state, inp):
        xb, bb, cb, db, dtb = inp    # (B,H,P),(B,H,N),(B,H,N),(B,H),(B,H)
        state = state * db[..., None, None] + jnp.einsum(
            "bhp,bhn->bhpn", xb * dtb[..., None], bb)
        y = jnp.einsum("bhpn,bhn->bhp", state, cb)
        return state, y

    state0 = jnp.zeros((B, H, P, N), jnp.float32)
    xs = (jnp.moveaxis(x, 1, 0).astype(jnp.float32),
          jnp.moveaxis(bq, 1, 0).astype(jnp.float32),
          jnp.moveaxis(cq, 1, 0).astype(jnp.float32),
          jnp.moveaxis(decay, 1, 0).astype(jnp.float32),
          jnp.moveaxis(dt, 1, 0).astype(jnp.float32))
    _, ys = jax.lax.scan(step, state0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype)
