"""Kernel micro-benchmarks: jnp-oracle wall time on the CPU (host numbers
that size the CPU fallbacks, not device speed) plus each op's FLOP count
from its shapes.  Device times come only from a run on the chip.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.types import QueryBatch, StoreView


def _time(f, *args, iters=5):
    f(*args)[0].block_until_ready() if isinstance(f(*args), tuple) else \
        jax.block_until_ready(f(*args))
    t0 = time.monotonic()
    for _ in range(iters):
        jax.block_until_ready(f(*args))
    return (time.monotonic() - t0) / iters


def main():
    key = jax.random.PRNGKey(0)
    rows = []

    # lsh_hash: n=8192, d=100, K=128 (multi-table)
    x = jax.random.normal(key, (8192, 100))
    a = jax.random.normal(key, (100, 128))
    b = jnp.zeros((128,))
    f = jax.jit(lambda x, a, b: ref.lsh_hash_ref(x, a, b, w=0.5))
    t = _time(f, x, a, b)
    flops = 2 * 8192 * 100 * 128
    rows.append(("lsh_hash_8192x100x128", t * 1e6, f"flops={flops}"))

    # bucket_search: R=512, N=4096, d=64, L=8
    q = jax.random.normal(key, (512, 64))
    p = jax.random.normal(key, (4096, 64))
    qb = jax.random.randint(key, (512, 16), 0, 64, dtype=jnp.int32)
    probe = jnp.ones((512, 8), jnp.int32)
    pb = jax.random.randint(key, (4096, 2), 0, 64, dtype=jnp.int32)
    gid = jnp.arange(4096, dtype=jnp.int32)
    pv = jnp.ones((4096,), jnp.int32)
    query = QueryBatch.build(q, qb, probe)
    store = StoreView.build(p, pb, gid, pv)
    f = jax.jit(lambda qb_, sv: ref.bucket_search_ref(
        query=qb_, store=sv, cr2=2.0, L=8))
    t = _time(f, query, store)
    flops = 2 * 512 * 4096 * 64
    rows.append(("bucket_search_512x4096", t * 1e6, f"flops={flops}"))

    # top-K variant: same scan, K=16 accumulator (the serving path)
    f = jax.jit(lambda qb_, sv: ref.bucket_search_ref(
        query=qb_, store=sv, cr2=2.0, L=8, K=16))
    t = _time(f, query, store)
    rows.append(("bucket_search_topk16_512x4096", t * 1e6,
                 f"flops={flops}"))

    # attention: B1 H8 S1024 dh64
    qq = jax.random.normal(key, (1, 8, 1024, 64), jnp.bfloat16)
    f = jax.jit(lambda q, k, v: ref.attention_ref(q, k, v, causal=True))
    t = _time(f, qq, qq, qq)
    flops = 4 * 8 * 1024 * 1024 * 64
    rows.append(("attention_1x8x1024x64", t * 1e6, f"flops={flops}"))

    # ssd_scan: B1 S1024 H4 P32 N32
    xs = jax.random.normal(key, (1, 1024, 4, 32)) * 0.3
    al = jnp.full((4,), -0.7)
    bb = jax.random.normal(key, (1, 1024, 4, 32)) * 0.3
    dt = jax.nn.softplus(jax.random.normal(key, (1, 1024, 4)))
    f = jax.jit(lambda *a: ref.ssd_scan_ref(*a))
    t = _time(f, xs, al, bb, bb, dt)
    rows.append(("ssd_scan_1x1024x4x32", t * 1e6, "seq_scan_ref"))

    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    return rows


if __name__ == "__main__":
    main()
