"""Serving pipeline benchmark: synchronous micro-batcher vs the
double-buffered async pipeline on the same steady-state query stream.

Runs the ACTUAL shard_map index in a subprocess with 8 host devices
(same harness as bench_distributed / bench_persist).  Reports:

  sync   -- ShardedLSHService: every bucket flush fetches its results
            before the next batch dispatches (host-blocking)
  async  -- AsyncLSHService: up to 2 micro-batches in flight; batch
            i+1's dispatch all_to_all overlaps batch i's bucket scan
            and return (jax async dispatch + donated slot rotation)

plus the async service's p50/p99 per-query latency, and verifies the
two answer streams are BITWISE identical before timing anything.

``main`` returns a metrics dict which ``run.py`` attaches to the CI
artifact; the full (non-smoke) lane gates async/sync steady-state
throughput >= 1.3x at 8 shards (the smoke lane only records it --
single-core CI containers cannot overlap device work).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap

_SCRIPT = """
import json, time
import jax, numpy as np
import jax.numpy as jnp
from repro.compat import make_mesh
from repro.core import LSHConfig, Scheme, DistributedLSHIndex
from repro.data import planted_random
from repro.serving import AsyncLSHService, ShardedLSHService

N = {n}
BATCHES = {batches}
BUCKET = {bucket}
D = 64
K = 10

mesh = make_mesh((8,), ("shard",))
cfg = LSHConfig(d=D, k=10, W=1.0, r=0.3, c=2.0, L=16, n_shards=8,
                scheme=Scheme.LAYERED, seed=0, n_tables=2)
data, q0, _ = planted_random(n=N, m=BUCKET, d=D, r=0.3, seed=0)
idx = DistributedLSHIndex(cfg, mesh, use_kernel=True, k_neighbors=K)
idx.build(jnp.asarray(data))
rng = np.random.default_rng(3)
stream = [np.asarray(q0)[rng.permutation(BUCKET)] for _ in range(BATCHES)]
metrics = {{}}

def drive(svc):
    handles = []
    for qs in stream:
        handles += svc.submit_batch(qs)
    svc.drain()
    return handles

# ---- bitwise equivalence on the stream, then per-service warmup ----
sync = ShardedLSHService(idx, bucket_size=BUCKET,
                         max_latency_ms=float("inf"), k_neighbors=K)
asvc = AsyncLSHService(idx, bucket_size=BUCKET,
                       max_latency_ms=float("inf"), k_neighbors=K,
                       pipeline_depth=2)
hs = drive(sync)
ha = drive(asvc)
for a, b in zip(hs, ha):
    assert np.array_equal(a.gids, b.gids) and np.array_equal(a.dists,
                                                             b.dists)
print(f"bitwise,{{len(hs)}} queries identical")

# ---- steady state: same stream, fresh stats ----
print("bench,queries,ms,qps")
t0 = time.monotonic()
drive(sync)
t_sync = time.monotonic() - t0
n_q = BATCHES * BUCKET
print(f"sync,{{n_q}},{{t_sync*1e3:.1f}},{{n_q/t_sync:.0f}}")

t0 = time.monotonic()
drive(asvc)
t_async = time.monotonic() - t0
print(f"async,{{n_q}},{{t_async*1e3:.1f}},{{n_q/t_async:.0f}}")
st = asvc.stats
assert st.inflight_peak >= 2, st.inflight_peak
asvc.close()

metrics["queries"] = n_q
metrics["sync_qps"] = round(n_q / t_sync, 1)
metrics["async_qps"] = round(n_q / t_async, 1)
metrics["speedup"] = round(t_sync / t_async, 3)
metrics["async_p50_ms"] = round(st.latency_p50_ms, 2)
metrics["async_p99_ms"] = round(st.latency_p99_ms, 2)
print(f"speedup,{{n_q}},,{{metrics['speedup']}}x "
      f"p50={{metrics['async_p50_ms']}}ms p99={{metrics['async_p99_ms']}}ms")
print("SERVING_JSON " + json.dumps(metrics))
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
    n, batches, bucket = (2048, 8, 64) if smoke else (16384, 32, 128)
    out = _run_script(_SCRIPT.format(n=n, batches=batches, bucket=bucket))
    for line in out.splitlines():
        if line.startswith("SERVING_JSON "):
            return json.loads(line[len("SERVING_JSON "):])
    raise RuntimeError(f"no SERVING_JSON line in bench_serving output:\n{out}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    main(smoke=args.smoke)
