"""Wall-clock benchmark of the ACTUAL shard_map distributed index (not the
analytic simulator) at small device counts, plus the Pallas-kernel search
path vs jnp. Runs in a subprocess with 8 host devices.

Three regimes:
  batch     -- one-shot build + batch query (the paper's MapReduce view):
               build/query time, live routed rows, static all_to_all wire
               bytes per scheme (the TPU-implementation view of Fig 4.1).
  streaming -- the serving view: a ShardedLSHService answers a mixed
               insert+query stream; reports steady-state throughput
               (queries/s, inserts/s), per-flush latency, routed
               rows/query and the per-shard load-balance trajectory.
  T-sweep   -- the fused multi-table view (``tables_sweep``, also
               ``--tables 1,2,4`` from the CLI): per table count, warm
               build/query latency, routed rows/query, recall@10 and the
               per-step collective count (constant in T by construction;
               the sweep asserts the fused result equals the
               single-machine union reference).
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import textwrap

_SCRIPT = """
import time
import jax, numpy as np
import jax.numpy as jnp
from repro.compat import make_mesh
from repro.core import (LSHConfig, Scheme, DistributedLSHIndex,
                        simulate_stream)
from repro.data import planted_random
from repro.serving import ServiceStats, ShardedLSHService

N, M, D = {n}, {m}, 64
data, queries, _ = planted_random(n=N, m=M, d=D, r=0.3, seed=0)
data, queries = jnp.asarray(data), jnp.asarray(queries)
mesh = make_mesh((8,), ("shard",))
print("scheme,phase,ms,rows,capacity_rows")
for scheme in (Scheme.SIMPLE, Scheme.LAYERED):
    cfg = LSHConfig(d=D, k=10, W=1.0, r=0.3, c=2.0, L=16, n_shards=8,
                    scheme=scheme, seed=0)
    idx = DistributedLSHIndex(cfg, mesh)
    t0 = time.monotonic(); br = idx.build(data); t_build = time.monotonic()-t0
    t0 = time.monotonic(); qr = idx.query(queries); t_q1 = time.monotonic()-t0
    t0 = time.monotonic(); qr = idx.query(queries); t_q2 = time.monotonic()-t0
    cap_rows = 8 * 8 * idx._query_capacity(M // 8)
    print(f"{{scheme.value}},build,{{t_build*1e3:.1f}},{{br.data_load.sum()}},")
    print(f"{{scheme.value}},query_warm,{{t_q2*1e3:.1f}},"
          f"{{int(qr.query_load.sum())}},{{cap_rows}}")
    assert qr.drops == 0 and br.drops == 0

# ---- top-K retrieval: K-sweep latency curve + recall@K vs brute force ----
from repro.core import lsh_topk_reference, nearest_neighbors, recall_at_k
print("scheme,K,query_warm_ms,recall_at_K")
cfg = LSHConfig(d=D, k=10, W=1.0, r=0.3, c=2.0, L=16, n_shards=8,
                scheme=Scheme.LAYERED, seed=0)
idx = DistributedLSHIndex(cfg, mesh)
idx.build(data)
_, true_idx = nearest_neighbors(np.asarray(data), np.asarray(queries), 32)
for K in (1, 4, 10, 32):
    idx.query(queries, k_neighbors=K)          # warm the K-specialised fn
    t0 = time.monotonic()
    qr = idx.query(queries, k_neighbors=K)
    t_q = time.monotonic() - t0
    rec = recall_at_k(qr.topk_gid, true_idx[:, :K])
    print(f"layered,{{K}},{{t_q*1e3:.1f}},{{rec:.3f}}")
# the distributed top-10 must equal the single-machine LSH reference
refd, refg = lsh_topk_reference(cfg, data, queries, 10)
qr10 = idx.query(queries, k_neighbors=10)
agree = float((qr10.topk_gid == refg).mean())
print(f"# top-10 gid agreement vs single-machine LSH reference: {{agree:.4f}}")
assert agree == 1.0, agree

# ---- streaming serving mix: grow the index while answering queries ----
print("scheme,qps,ips,p50_ms,rows_per_query,load_skew,occupancy,drops")
STEPS, INS, BUCKET = {steps}, {ins}, {bucket}
for scheme in (Scheme.SIMPLE, Scheme.LAYERED):
    cfg = LSHConfig(d=D, k=10, W=1.0, r=0.3, c=2.0, L=16, n_shards=8,
                    scheme=scheme, seed=0)
    idx = DistributedLSHIndex(cfg, mesh)
    n0 = N - STEPS * INS
    idx.build(data[:n0], capacity=idx._store_capacity(N))
    svc = ShardedLSHService(idx, bucket_size=BUCKET, max_latency_ms=50.0)
    # warm both compiled paths
    svc.insert(data[n0:n0 + INS]); svc.submit_batch(
        np.asarray(queries[:BUCKET])); svc.drain()
    svc.stats = ServiceStats()
    lat = []
    for t in range(1, STEPS):
        lo = n0 + t * INS
        svc.insert(data[lo:lo + INS])
        sel = (np.arange(BUCKET) + t * BUCKET) % M
        t0 = time.monotonic()
        svc.submit_batch(np.asarray(queries)[sel])
        svc.drain()
        lat.append(time.monotonic() - t0)
    st = svc.stats
    load = svc.shard_load()
    skew = load.max() / max(load.mean(), 1)
    print(f"{{scheme.value}},{{st.queries / sum(lat):.0f}},"
          f"{{st.inserts_per_s:.0f}},{{np.median(lat)*1e3:.1f}},"
          f"{{st.routed_rows/max(st.queries,1):.2f}},{{skew:.2f}},"
          f"{{st.occupancy:.2f}},{{st.drops}}")
    assert st.drops == 0
    # analytic cross-check: same mix through the simulator
    rep = simulate_stream(cfg, data, queries, n_prefix=n0,
                          insert_batch=INS, query_batch=BUCKET)
    print(f"# analytic: {{rep.summary()}}")
"""


_TABLES_SCRIPT = """
import json, time
import jax, numpy as np
import jax.numpy as jnp
from repro.analysis import jaxpr_pass, load_contracts
from repro.analysis.check import query_stages
from repro.compat import make_mesh
from repro.core import (LSHConfig, Scheme, DistributedLSHIndex,
                        lsh_topk_reference, nearest_neighbors, recall_at_k,
                        simulate, COLLECTIVES_PER_QUERY)

N, M, D, K = {n}, {m}, 64, 10
TABLES = {tables}
from repro.data import planted_random
data, queries, _ = planted_random(n=N, m=M, d=D, r=0.3, seed=0)
data, queries = jnp.asarray(data), jnp.asarray(queries)
mesh = make_mesh((8,), ("shard",))
_, true_idx = nearest_neighbors(np.asarray(data), np.asarray(queries), K)
contracts = load_contracts()
budgets = contracts["jaxpr"]["collectives"]
print("scheme,T,build_ms,query_cold_ms,query_warm_ms,jaxpr_eqns,"
      "rows_per_query,recall_at_10,collectives_per_query,union_exact")
trace = {{}}
for T in TABLES:
    cfg = LSHConfig(d=D, k=10, W=1.0, r=0.3, c=2.0, L=16, n_shards=8,
                    scheme=Scheme.LAYERED, seed=0, n_tables=T)
    idx = DistributedLSHIndex(cfg, mesh, k_neighbors=K)
    t0 = time.monotonic(); br = idx.build(data); t_b = time.monotonic() - t0
    # cold = trace + compile + run; jaxpr size must be FLAT in T (the
    # gather-by-table hash pass does one table's work per routed row)
    t0 = time.monotonic(); idx.query(queries); t_cold = time.monotonic()-t0
    trace[f"compile_s_T{{T}}"] = round(t_cold, 3)
    st = idx.store
    # structural counters from the analyzer (primitive identity, not
    # text regex), summed over the query's three staged programs; counts
    # are recorded in the --json trace and gated by check_regression
    # (ratio for eqns, exact for collectives)
    n_eqns = n_a2a = 0
    for phase, fn, args in query_stages(idx, queries, K, G=4):
        qj = jax.make_jaxpr(fn)(*args)
        n_eqns += jaxpr_pass.eqn_count(qj)
        qc = jaxpr_pass.collective_counts(qj)
        assert not jaxpr_pass.check_collectives(qc, budgets[phase]), (T, qc)
        n_a2a += qc.get("all_to_all", 0)
    trace[f"jaxpr_eqns_T{{T}}"] = n_eqns
    trace[f"collectives_query_T{{T}}"] = n_a2a
    ins = idx._make_insert_fn(M // 8, idx._dispatch_capacity(M // 8 * T),
                              st.capacity, st.n_sorted)
    ic = jaxpr_pass.collective_counts(jax.make_jaxpr(ins)(
        data[:M], jnp.arange(M, dtype=jnp.int32), jnp.ones(M, bool),
        st.x, st.packed, st.gid, st.table, st.key, st.valid))
    assert not jaxpr_pass.check_collectives(ic, budgets["insert"]), (T, ic)
    trace[f"collectives_insert_T{{T}}"] = ic.get("all_to_all", 0)
    jaxpr_eqns = trace[f"jaxpr_eqns_T{{T}}"]
    t0 = time.monotonic(); qr = idx.query(queries); t_q = time.monotonic()-t0
    assert br.drops == 0 and qr.drops == 0, (T, br.drops, qr.drops)
    rec = recall_at_k(qr.topk_gid, true_idx)
    # the fused T-table result must equal the single-machine UNION
    # reference exactly (same candidates, same (dist, gid) merge order)
    _, refg = lsh_topk_reference(cfg, data, queries, K)
    exact = bool(np.array_equal(qr.topk_gid, refg))
    rep = simulate(cfg, data, queries)
    assert abs(qr.fq.mean() - rep.fq_mean) < 1e-6
    print(f"layered,{{T}},{{t_b*1e3:.1f}},{{t_cold*1e3:.1f}},"
          f"{{t_q*1e3:.1f}},{{jaxpr_eqns}},"
          f"{{qr.fq.mean():.2f}},{{rec:.3f}},{{COLLECTIVES_PER_QUERY}},"
          f"{{exact}}")
    assert exact, T
eqns = {{int(k.split("_T")[1]): v for k, v in trace.items()
        if k.startswith("jaxpr_eqns")}}
flat = jaxpr_pass.check_flatness(
    eqns, contracts["jaxpr"]["flatness"]["max_ratio"], "query")
assert not flat, (flat, trace)
print("TRACE_JSON " + json.dumps(trace))
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


def main(smoke: bool = False):
    sizes = dict(n=2048, m=256, steps=2, ins=128, bucket=64) if smoke \
        else dict(n=16384, m=1024, steps=8, ins=512, bucket=128)
    return _run_script(_SCRIPT.format(**sizes))


def tables_sweep(smoke: bool = False, tables=(1, 2, 4)) -> dict:
    """Fused multi-table sweep: latency / traffic / recall@10 vs T, with
    an exact-agreement check against the single-machine union reference
    and the constant per-step collective count.

    Also measures the query step's trace cost per T with the analyzer's
    structural counters -- ``jaxpr_eqns_T<t>`` (equation count; FLAT in
    T with the gather-by-table hash pass, asserted at the manifest's
    flatness ratio), ``collectives_{insert,query}_T<t>`` (fused
    all_to_all counts, exact-checked against the per-phase budgets in
    ``contracts.json``) and ``compile_s_T<t>`` (cold trace + compile +
    run wall time) -- and returns them as a dict so ``run.py --smoke
    --json`` can record them for the CI regression gate
    (``check_regression`` ratio-gates jaxpr_eqns_* and exact-gates
    collectives_*)."""
    import json
    sizes = dict(n=1024, m=64) if smoke else dict(n=4096, m=256)
    out = _run_script(_TABLES_SCRIPT.format(tables=tuple(tables), **sizes))
    for line in out.splitlines():
        if line.startswith("TRACE_JSON "):
            return json.loads(line[len("TRACE_JSON "):])
    raise RuntimeError(f"no TRACE_JSON line in tables_sweep output:\n{out}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--tables", default=None,
                    help="comma list, e.g. 1,2,4: run ONLY the fused "
                         "multi-table sweep at those table counts")
    args = ap.parse_args()
    if args.tables:
        tables_sweep(smoke=args.smoke,
                     tables=tuple(int(t) for t in args.tables.split(",")))
    else:
        main(smoke=args.smoke)
        tables_sweep(smoke=args.smoke)
