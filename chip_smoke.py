#!/usr/bin/env python3
"""Smoke test of the served Layered-LSH path on a TPU, at deployment size.

Drives the system once through the calls a deployment makes, at the
ANN-Benchmarks ``sift-128-euclidean`` shape (1,000,000 base vectors,
128-d float32, recall@10), with data generated from ``--seed``:

  1. device check: a TPU, or ``--cpu`` for a rehearsal at a tiny size;
  2. build the index on a one-device mesh with the Pallas kernels,
     ``compact()`` it into the bucket-sorted layout (CSR gather kernel),
     stream an insert into the unsorted tail (full-scan kernel) and
     delete a few hundred gids, through ``AsyncLSHService``;
  3. serve batches of 64 queries (8 on one chip) through
     ``AsyncLSHService``;
  4. check: zero drops, the Mosaic kernels (``tpu_custom_call``) in the
     compiled query-scan step, top-10 agreement with
     ``lsh_topk_reference`` over the live set, and recall@10 against the
     exact ``nearest_neighbors``;
  5. print the device kind, HBM in use, build/compact/compile seconds and
     the smoke latencies (not benchmark numbers).

Every failed check exits non-zero before the last line is printed.  The
last stdout line is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

``--chips 4`` runs only the sharded path and its comparison: an S=4 mesh
over four chips at 4,000,000 points (each chip holds what the one-chip
run holds), each shard on its own device, zero drops, and the same top-10
agreement check.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # four chips of one host
  python chip_smoke.py --cpu        # CPU rehearsal (interpret-mode kernels)

The process holds the chip while it runs: start no other JAX process on
the same chip meanwhile.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# The deployment: ANN-Benchmarks sift-128-euclidean shape, one table of
# the paper's Layered LSH with 16 entropy probes, top-10 answers.
D, L, K = 128, 16, 10
N_PER_CHIP = 1_000_000
# Hash parameters for planted-random data (coordinates N(0, 1/d), so
# points sit ~sqrt(2) apart and a query's planted source r away): W and k
# give buckets of a few thousand points, and cr = c*r = 1.5 caps little,
# so every query fills its top-10 from its candidates.
HASH_K, W, R, C = 10, 3.0, 0.3, 5.0
# Agreement with the reference.  Both sides hash and measure distances
# at lax.Precision.HIGHEST (f32) on the same device; they still differ in
# accumulation order, which can move a point across a bucket boundary or
# swap two candidates tied to the last bits at rank K.
MIN_PAIR_AGREEMENT = 0.999     # reference (query, gid) pairs served
MAX_DIST_ERR = 1e-4            # |served - reference| distance, same gid
FAR = 100.0                    # deleted rows sit here in the reference


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check(ok: bool, msg: str) -> None:
    print(f"check {'ok  ' if ok else 'FAIL'} {msg}", flush=True)
    if not ok:
        fail(msg)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse on the CPU at a tiny size")
    args = ap.parse_args(argv)
    S = args.chips
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count={S}")
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.compat import make_mesh
    from repro.compile_cache import enable_compile_cache
    from repro.core import (DistributedLSHIndex, LSHConfig, Scheme,
                            lsh_topk_reference, nearest_neighbors,
                            recall_at_k)
    from repro.data import planted_random
    from repro.serving import AsyncLSHService

    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.cpu:
        fail(f"no TPU: JAX sees {dev.platform!r} devices "
             f"(--cpu runs the rehearsal)")
    if len(devices) < S:
        fail(f"--chips {S} needs {S} devices, JAX sees {len(devices)}")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={cache}", flush=True)

    if args.cpu:
        n_base, n_ins, n_del, n_batches = 2048 * S, 128, 32, 2
    else:   # the four-chip run checks fewer batches against its 4M reference
        n_base, n_ins, n_del, n_batches = N_PER_CHIP * S, 4096, 300, 8 // S
    bucket = 64
    n_all, m = n_base + n_ins, n_batches * bucket
    cfg = LSHConfig(d=D, k=HASH_K, W=W, r=R, c=C, L=L, n_shards=S,
                    scheme=Scheme.LAYERED, seed=args.seed)
    print(f"config: n_base={n_base} n_insert={n_ins} n_delete={n_del} d={D} "
          f"shards={S} tables=1 L={L} K={K} k={HASH_K} W={W} r={R} c={C} "
          f"(cr={C * R}) scheme=layered seed={args.seed} "
          f"queries={n_batches}x{bucket}", flush=True)
    print(f"precision: hashing (x@A, G), kernel distance matmul and "
          f"reference distances at lax.Precision.HIGHEST (f32) on "
          f"{dev.platform}; agreement demanded: >= {MIN_PAIR_AGREEMENT} of "
          f"the reference's (query, gid) pairs served, matched distances "
          f"within {MAX_DIST_ERR}", flush=True)

    t = time.perf_counter()
    data, queries, planted = planted_random(n_all, m, d=D, r=R,
                                            seed=args.seed)
    print(f"data: generated in {time.perf_counter() - t:.1f}s "
          f"(set-up)", flush=True)

    # ---- phase 2: build, compact, stream an insert and deletes --------
    mesh = make_mesh((S,), ("shard",), devices=devices[:S])
    idx = DistributedLSHIndex(cfg, mesh, use_kernel=True, k_neighbors=K)
    t = time.perf_counter()
    # keep no BuildResult: it would pin the pre-compact store in HBM
    drops = idx.build(data[:n_base]).drops
    jax.block_until_ready(idx.store.x)
    t_build = time.perf_counter() - t
    check(drops == 0, f"build drops == 0 (got {drops})")
    t = time.perf_counter()
    idx.compact()
    jax.block_until_ready(idx.store.x)
    t_compact = time.perf_counter() - t
    print(f"build: {n_base} points in {t_build:.1f}s, compact "
          f"{t_compact:.1f}s (compile included), layout {idx.layout}",
          flush=True)

    svc = AsyncLSHService(idx, bucket_size=bucket, max_latency_ms=1e3,
                          k_neighbors=K)
    try:
        ins = svc.insert(data[n_base:]).result()
        check(ins.drops == 0 and ins.n_inserted == n_ins,
              f"tail insert of {n_ins}: drops == 0, all stored "
              f"(drops={ins.drops}, stored={ins.n_inserted})")
        rng = np.random.default_rng(args.seed)
        deleted = np.concatenate([
            rng.choice(n_base, n_del - n_del // 4, replace=False),
            n_base + rng.choice(n_ins, n_del // 4, replace=False)])
        dres = svc.delete(deleted).result()
        check(dres.n_points == n_del,
              f"delete {n_del} gids (sorted region and tail): "
              f"{dres.n_points} removed")
        live = idx.n_live
        check(live == n_all - n_del and live >= n_base,
              f"live points on device: {live} (>= {n_base})")
        st = idx.store
        store_bytes = sum(int(a.nbytes) for a in (
            st.x, st.packed, st.gid, st.table, st.key, st.valid,
            st.bucket_start, st.bucket_end))
        print(f"store: capacity {st.capacity} rows/shard, sorted region "
              f"{st.n_sorted} rows, x {tuple(st.x.shape)} float32, "
              f"{store_bytes} bytes over {S} device(s)", flush=True)
        shard_devs = {s.device for s in st.x.addressable_shards}
        check(len(shard_devs) == S
              and all(s.data.shape[0] == 1 for s in st.x.addressable_shards),
              f"store sharded one block per device over {len(shard_devs)} "
              f"distinct device(s)")

        # ---- phase 3: serve query batches -----------------------------
        lat, handles = [], []
        for b in range(n_batches):
            t = time.perf_counter()
            hs = svc.submit_batch(queries[b * bucket:(b + 1) * bucket])
            for h in hs:
                h.result()
            lat.append(time.perf_counter() - t)
            handles += hs
        svc.drain()
        stats = svc.stats
    finally:
        svc.close()
    got_g = np.stack([h.gids for h in handles])
    got_d = np.stack([h.dists for h in handles])
    check(stats.drops == 0 and stats.queries == m,
          f"served {stats.queries} queries with drops == 0 "
          f"(drops={stats.drops})")
    for d_ in devices[:S]:      # the served index, before any reference
        ms = d_.memory_stats() or {}
        print(f"memory: {d_} bytes_in_use={ms.get('bytes_in_use')} "
              f"peak_bytes_in_use={ms.get('peak_bytes_in_use')}", flush=True)

    # ---- phase 4: the compiled scan step holds the Mosaic kernels -----
    if not args.cpu:
        scan_key = next(k for k in idx._query_fns if k[0] == "scan")
        disp = idx.query_dispatch(jnp.asarray(queries[:bucket]))
        st = idx.store
        hlo = idx._query_fns[scan_key].lower(
            disp.recv, st.x, st.packed, st.gid, st.table, st.valid,
            st.bucket_start, st.bucket_end).compile().as_text()
        check("tpu_custom_call" in hlo,
              f"compiled query-scan step contains tpu_custom_call "
              f"({hlo.count('tpu_custom_call')} sites): Mosaic kernels, "
              f"no interpreter, no jnp oracle")

    # ---- reference over the live set: deleted rows moved out of reach --
    live_data = np.array(data, copy=True)
    live_data[deleted] = FAR
    live_dev = jnp.asarray(live_data)
    t = time.perf_counter()
    ref_d, ref_g = [], []
    for b in range(n_batches):
        # the service seeds each bucket's entropy offsets by slot id, so
        # the reference runs bucket by bucket
        rd, rg = lsh_topk_reference(cfg, live_dev,
                                    queries[b * bucket:(b + 1) * bucket], K,
                                    data_chunk=1 << 16)
        ref_d.append(rd)
        ref_g.append(rg)
    ref_d, ref_g = np.concatenate(ref_d), np.concatenate(ref_g)
    IMAX = np.iinfo(np.int32).max
    ref_pairs = {(q, int(g)) for q, row in enumerate(ref_g)
                 for g in row if g != IMAX}
    got_pairs = {(q, int(g)) for q, row in enumerate(got_g)
                 for g in row if g != IMAX}
    agree = len(ref_pairs & got_pairs) / max(len(ref_pairs), 1)
    extra = len(got_pairs - ref_pairs)
    qi, i, j = np.nonzero((got_g[:, :, None] == ref_g[:, None, :])
                          & (got_g[:, :, None] != IMAX))
    dist_err = float(np.max(np.abs(got_d[qi, i] - ref_d[qi, j]),
                            initial=0.0))
    exact_rows = float(np.mean(np.all(got_g == ref_g, axis=1)))
    print(f"reference: lsh_topk_reference over {n_all - n_del} live points "
          f"in {time.perf_counter() - t:.1f}s; {len(ref_pairs)} pairs "
          f"({len(ref_pairs) / m:.2f} per query), rows identical "
          f"{exact_rows:.4f}", flush=True)
    check(agree >= MIN_PAIR_AGREEMENT
          and extra <= (1 - MIN_PAIR_AGREEMENT) * len(ref_pairs),
          f"top-{K} agreement with lsh_topk_reference: {agree:.5f} of "
          f"reference pairs served, {extra} served pairs not in it")
    check(dist_err <= MAX_DIST_ERR,
          f"matched distances agree: max |err| {dist_err:.3g} <= "
          f"{MAX_DIST_ERR}")
    check(not np.isin(got_g, deleted).any(), "no deleted gid served")

    if S == 1:
        t = time.perf_counter()
        _, true_g = nearest_neighbors(live_dev, queries, K)
        rec = recall_at_k(got_g, true_g)
        rec_ref = recall_at_k(ref_g, true_g)
        alive = ~np.isin(planted, deleted)
        hit1 = float(np.mean((got_g == planted[:, None]).any(1)[alive]))
        print(f"recall@{K} vs exact nearest_neighbors "
              f"({time.perf_counter() - t:.1f}s): served {rec:.4f}, "
              f"reference {rec_ref:.4f}; planted source in top-{K}: "
              f"{hit1:.4f}", flush=True)
        check(rec > 0 and abs(rec - rec_ref) <= 1e-2,
              f"recall@{K} {rec:.4f} non-zero and within 0.01 of the "
              f"reference's {rec_ref:.4f}")

    # ---- phase 5: supporting data --------------------------------------
    print(f"smoke timings (host clock, not benchmark numbers): build "
          f"{t_build:.2f}s, compact {t_compact:.2f}s, first query batch "
          f"(compile included) {lat[0]:.2f}s, p50 batch latency after it "
          f"{np.median(lat[1:] or lat) * 1e3:.1f}ms over "
          f"{max(len(lat) - 1, 1)} batches", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
