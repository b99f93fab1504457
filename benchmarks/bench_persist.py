"""Durability benchmarks: snapshot / restore / WAL-replay throughput,
recovery time vs store size, and the elastic S -> S' re-shard cost.

Runs the ACTUAL shard_map index in a subprocess with 8 host devices
(same harness as bench_distributed).  Reports:

  snapshot    -- live-rows-only serialise + atomic commit (MB, MB/s)
  restore     -- snapshot -> live index on the SAME shard count
  elastic     -- snapshot (S=8) -> live index on S'=4 (host re-route by
                 stored Key, no re-hash) and back
  recover     -- restore + WAL-tail replay (points/s through the routed
                 insert path), at two store sizes (recovery time scales
                 with live rows + tail length)

``main`` returns a metrics dict which ``run.py --smoke --json`` attaches
to the CI artifact (wall-time gated by check_regression like every other
bench).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap

_SCRIPT = """
import json, os, tempfile, time
import jax, numpy as np
import jax.numpy as jnp
from repro.compat import make_mesh
from repro.core import LSHConfig, Scheme, DistributedLSHIndex
from repro.data import planted_random
from repro.serving import ShardedLSHService
from repro import persist

SIZES = {sizes}
D = 64
mesh = make_mesh((8,), ("shard",))
mesh4 = make_mesh((4,), ("shard",), devices=jax.devices()[:4])
metrics = {{}}
print("bench,n_points,ms,mb,throughput")

def dir_mb(d):
    total = 0
    for root, _, files in os.walk(d):
        total += sum(os.path.getsize(os.path.join(root, f)) for f in files)
    return total / 1e6

for N in SIZES:
    cfg = LSHConfig(d=D, k=10, W=1.0, r=0.3, c=2.0, L=16, n_shards=8,
                    scheme=Scheme.LAYERED, seed=0, n_tables=2)
    data, queries, _ = planted_random(n=N, m=64, d=D, r=0.3, seed=0)
    data, queries = jnp.asarray(data), jnp.asarray(queries)
    idx = DistributedLSHIndex(cfg, mesh)
    idx.build(data, capacity=idx._store_capacity(2 * N * cfg.n_tables))
    idx.delete(np.arange(0, N, 7))        # tombstones: snapshot compacts
    qr = idx.query(queries, k_neighbors=10)

    with tempfile.TemporaryDirectory() as tmp:
        # ---- snapshot (live rows only, atomic) ----
        t0 = time.monotonic()
        persist.snapshot(idx, tmp)
        t_snap = time.monotonic() - t0
        mb = dir_mb(tmp)
        print(f"snapshot,{{N}},{{t_snap*1e3:.1f}},{{mb:.2f}},"
              f"{{mb/t_snap:.1f}}MB/s")

        # ---- restore, same shard count ----
        t0 = time.monotonic()
        r = persist.restore(tmp, mesh)
        t_rest = time.monotonic() - t0
        qs = r.query(queries, k_neighbors=10)
        assert np.array_equal(qs.topk_gid, qr.topk_gid)
        print(f"restore,{{N}},{{t_rest*1e3:.1f}},{{mb:.2f}},"
              f"{{r.n_live/t_rest:.0f}}rows/s")

        # ---- elastic S=8 -> S'=4 (host re-route by stored Key) ----
        t0 = time.monotonic()
        r4 = persist.restore(tmp, mesh4, n_shards=4)
        t_el = time.monotonic() - t0
        q4 = r4.query(queries, k_neighbors=10)
        assert np.array_equal(q4.topk_gid, qr.topk_gid)
        print(f"elastic_8to4,{{N}},{{t_el*1e3:.1f}},{{mb:.2f}},"
              f"{{r4.n_live/t_el:.0f}}rows/s")

        # ---- recover: snapshot + WAL tail replay ----
        wal = persist.WriteAheadLog(persist.wal_path(tmp))
        svc = ShardedLSHService(idx, bucket_size=64, wal=wal)
        tail = max(N // 4, 64)
        extra, _, _ = planted_random(n=tail, m=8, d=D, r=0.3, seed=1)
        for lo in range(0, tail, 256):
            svc.insert(jnp.asarray(extra[lo:lo + 256]))
        svc.delete(np.arange(1, N, 101))
        t0 = time.monotonic()
        # match the live store's reservation so replay cannot hit append
        # drops the original stream did not
        rr = persist.recover(tmp, mesh, capacity=idx.store.capacity)
        t_rec = time.monotonic() - t0
        print(f"recover,{{N}},{{t_rec*1e3:.1f}},,"
              f"{{rr.replayed_points/t_rec:.0f}}pts/s "
              f"({{rr.replayed_inserts}}ins+{{rr.replayed_deletes}}del)")
        assert rr.index.n_live == idx.n_live
    if N == SIZES[-1]:
        metrics["snapshot_ms"] = round(t_snap * 1e3, 1)
        metrics["restore_ms"] = round(t_rest * 1e3, 1)
        metrics["elastic_ms"] = round(t_el * 1e3, 1)
        metrics["recover_ms"] = round(t_rec * 1e3, 1)
        metrics["snapshot_mb"] = round(mb, 2)
print("PERSIST_JSON " + json.dumps(metrics))
"""


def _run_script(script: str, timeout: int = 1800) -> str:
    env = dict(os.environ)
    # a CPU-lane tool: the child never contends for an accelerator the
    # parent process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(repo, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, env=env, timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    print(out.stdout.strip())
    return out.stdout


def main(smoke: bool = False) -> dict:
    sizes = (1024,) if smoke else (4096, 16384)
    out = _run_script(_SCRIPT.format(sizes=tuple(sizes)))
    for line in out.splitlines():
        if line.startswith("PERSIST_JSON "):
            return json.loads(line[len("PERSIST_JSON "):])
    raise RuntimeError(f"no PERSIST_JSON line in bench_persist output:\n{out}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    main(smoke=args.smoke)
