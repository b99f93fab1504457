"""Serving driver: stand up the retrieval service (LM embedder +
distributed Layered-LSH index) and run batched query traffic, reporting
the paper's metrics (rows/query, load balance) alongside latency.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma-7b --reduced \
      --docs 2048 --batches 4
(multi-device: XLA_FLAGS=--xla_force_host_platform_device_count=8)

On an accelerator the index searches with the Pallas kernels (the
``DistributedLSHIndex`` default); compiled programs persist in the cache
that ``repro.compile_cache`` sets up.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import persist
from repro.compat import make_mesh
from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.core import Scheme
from repro.models import init_params
from repro.serving import RetrievalService


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-7b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--docs", type=int, default=2048)
    ap.add_argument("--batches", type=int, default=4)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--scheme", default="layered",
                    choices=[s.value for s in Scheme])
    ap.add_argument("--L", type=int, default=16)
    ap.add_argument("--tables", type=int, default=1,
                    help="fused hash tables (recall lever; same number of"
                         " collectives per step for any value)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--snapshot-dir", default=None,
                    help="durability: WAL every write there, snapshot the "
                         "index, and WARM-RESTART from the latest snapshot "
                         "+ WAL tail when one exists (works across a "
                         "different device count: elastic re-shard)")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="snapshot (and truncate the WAL) every N query "
                         "batches; 0 = only the boot snapshot")
    ap.add_argument("--pipelined", action="store_true",
                    help="serve through AsyncLSHService: double-buffered "
                         "query pipeline + background snapshots "
                         "(bitwise-identical results)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    n_dev = len(jax.devices())
    mesh = make_mesh((n_dev,), ("shard",))

    key = jax.random.PRNGKey(1)
    doc_tokens = jax.random.randint(key, (args.docs, 32), 0, cfg.vocab)
    t0 = time.monotonic()
    # the service bucket must divide by the mesh's shard count; round the
    # requested batch size up so any --batch-size serves (pad-to-bucket
    # absorbs the difference)
    bucket = -(-args.batch_size // n_dev) * n_dev
    svc, rr = RetrievalService.recover_or_build(
        cfg, params, doc_tokens, mesh, snapshot_dir=args.snapshot_dir,
        bucket_size=bucket, r=0.2, L=args.L, k=8, W=0.5,
        scheme=Scheme(args.scheme), seed=args.seed, n_tables=args.tables,
        pipelined=args.pipelined)
    if rr is not None:
        # warm restart: snapshot + WAL tail instead of re-embed + rebuild
        print(f"[serve] WARM restart from {args.snapshot_dir} "
              f"(step {rr.step}, {rr.index.n_live} rows, "
              f"{rr.replayed_inserts + rr.replayed_deletes} WAL batches "
              f"replayed) in {time.monotonic() - t0:.1f}s")
    else:
        br = svc.index.build_result
        print(f"[serve] built index: {args.docs} docs, "
              f"{time.monotonic() - t0:.1f}s, "
              f"load max/avg="
              f"{br.data_load.max() / max(br.data_load.mean(), 1):.1f}, "
              f"drops={br.drops}")
        if args.snapshot_dir:
            print(f"[serve] boot snapshot -> {args.snapshot_dir}")

    lat = []
    for b in range(args.batches):
        kq = jax.random.fold_in(jax.random.PRNGKey(2), b)
        src = jax.random.randint(kq, (args.batch_size,), 0, args.docs)
        qtok = doc_tokens[src]
        t0 = time.monotonic()
        gids, dists, handles = svc.query(qtok)
        lat.append(time.monotonic() - t0)
        if (args.snapshot_dir and args.snapshot_every
                and (b + 1) % args.snapshot_every == 0):
            if args.pipelined:
                # background snapshot: the engine thread fetches a
                # consistent point, a writer thread does the file I/O
                svc.service.snapshot(args.snapshot_dir).result()
            else:
                persist.snapshot(svc.index, args.snapshot_dir,
                                 wal=svc.service.wal)
    svc.close()
    st = svc.service.stats
    assert st.drops == 0
    n = args.batches * args.batch_size
    print(f"[serve] {n} queries: p50 batch latency "
          f"{np.median(lat) * 1e3:.0f}ms, rows/query "
          f"{st.routed_rows / max(st.queries, 1):.2f} "
          f"(simple-LSH would ship ~{args.L}), scheme={args.scheme}")
    print(f"[serve] {st.summary()}")


if __name__ == "__main__":
    main()
