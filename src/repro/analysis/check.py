"""SPMD contract gate: ``python -m repro.analysis.check``.

Traces the real ``DistributedLSHIndex`` insert and delete steps and the
query's three staged steps (dispatch / scan / return) on 8 XLA host
devices at T in {1, 2, 4} (the manifest's ``check_config``), runs the
three analysis passes against ``contracts.json``, writes a
machine-readable JSON report, and exits nonzero on any violation.  CI
runs this in the fast lane and uploads the report next to the bench
baseline;
``benchmarks/check_regression.py --contracts`` refuses to gate without
it.

``--seed-violation {extra-collective,broken-donation,jaxpr-growth,
host-sync}`` deliberately injects one violation of each contract class
so the gate itself stays falsifiable (exercised by
``tests/test_contracts.py``).

No jax import may happen at module level: XLA host-device count must be
configured from the manifest before the backend initialises.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from typing import Any, Dict, List

from repro.analysis import manifest, repolint

SEEDABLE = ("extra-collective", "broken-donation", "jaxpr-growth", "host-sync")

_SEEDED_HOT_FILE = """\
import numpy as np

def query_shard(batch):
    # seeded violation: host sync inside a hot-path step function
    return np.asarray(batch)
"""


def _run_repolint(contracts: Dict[str, Any], root: str,
                  seed: str | None) -> Dict[str, Any]:
    cfg = contracts["repolint"]
    report = repolint.scan(root, cfg)
    if seed == "host-sync":
        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "seeded_hot_path.py")
            with open(path, "w") as f:
                f.write(_SEEDED_HOT_FILE)
            extra = repolint.scan_files([path], cfg, rel_root=tmp)
        report["violations"].extend(v.as_dict() for v in extra)
        report["files_scanned"] += 1
    return report


def query_stages(idx, queries, K: int, donate: bool = False,
                 G: int | None = None) -> List[tuple]:
    """The index's three compiled query steps for ``queries``, with
    arguments to trace or lower them: ``[(phase, fn, args)]`` for
    dispatch, scan and return.  ``G`` defaults to the index's own gather
    window."""
    import jax.numpy as jnp

    st, cfg = idx.store, idx.cfg
    S, m = cfg.n_shards, queries.shape[0]
    Cq = idx._query_capacity(m // S)
    if G is None:
        G = idx._gather_window(S * Cq * cfg.L)
    return [
        ("query_dispatch", idx._make_query_dispatch_fn(m, Cq, donate),
         (queries, jnp.arange(m, dtype=jnp.int32))),
        ("query_scan",
         idx._make_query_scan_fn(m, Cq, K, st.n_sorted, G,
                                 idx._scan_extent()),
         (jnp.zeros((S * S * Cq, cfg.d + 2), jnp.int32), st.x, st.packed,
          st.gid, st.table, st.valid, st.bucket_start, st.bucket_end)),
        ("query_return", idx._make_query_return_fn(m, K),
         (jnp.zeros((S * m, 2 * K + 1), jnp.int32),)),
    ]


def _run_compiled_passes(contracts: Dict[str, Any], seed: str | None,
                         report: Dict[str, Any]) -> List[str]:
    """Trace + compile the real step fns; returns violation messages."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.analysis import hlo_pass, jaxpr_pass
    from repro.compat import make_mesh
    from repro.core import DistributedLSHIndex, LSHConfig, Scheme
    from repro.data import planted_random
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    cc = contracts["check_config"]
    S = int(cc["n_shards"])
    if jax.device_count() < S:
        raise RuntimeError(
            f"need {S} devices, have {jax.device_count()}; run via "
            f"python -m repro.analysis.check (it sets "
            f"--xla_force_host_platform_device_count before importing jax)")
    mesh = make_mesh((S,), ("shard",))
    data, queries, _ = planted_random(n=cc["n"], m=cc["m"], d=cc["d"],
                                      r=cc["r"], seed=cc["seed"])
    data, queries = jnp.asarray(data), jnp.asarray(queries)
    m, K, G_probe = int(cc["m"]), int(cc["k_neighbors"]), int(cc["probe"])

    violations: List[str] = []
    PHASES = ("insert", "delete",
              "query_dispatch", "query_scan", "query_return")
    phases: Dict[str, Dict[str, Any]] = {p: {} for p in PHASES}
    eqns: Dict[str, Dict[int, int]] = {p: {} for p in PHASES}
    hlo_T = int(cc["hlo_tables"])
    hlo_ctx: Dict[str, Any] = {}

    for T in cc["tables"]:
        cfg = LSHConfig(d=cc["d"], k=cc["k"], W=cc["W"], r=cc["r"], c=cc["c"],
                        L=cc["L"], n_shards=S, scheme=Scheme.LAYERED,
                        seed=cc["seed"], n_tables=T)
        idx = DistributedLSHIndex(cfg, mesh, use_kernel=True, k_neighbors=K)
        idx.build(data)
        st = idx.store
        n_loc = m // S

        ifn = idx._make_insert_fn(n_loc, idx._dispatch_capacity(n_loc * T),
                                  st.capacity, st.n_sorted)
        iargs = (data[:m], jnp.arange(m, dtype=jnp.int32),
                 jnp.ones(m, bool), st.x, st.packed, st.gid, st.table,
                 st.key, st.valid)

        n_del = 8
        dfn = idx._make_delete_fn(n_del, st.capacity, st.n_sorted)
        padded = np.full((n_del,), np.iinfo(np.int32).max, np.int32)
        dargs = (jnp.asarray(padded), st.valid, st.gid)

        # the query: its three compiled steps, as both front ends run them
        stages = query_stages(idx, queries, K)
        if seed == "jaxpr-growth":
            # inject per-table work: eqn count now grows linearly in T
            def rtrace(*a, _rf=stages[2][1], _T=T):
                out = _rf(*a)
                d = out[0]
                for _ in range(120 * (_T - 1)):
                    d = jnp.sin(d)
                return (d,) + tuple(out[1:])
            stages[2] = ("query_return", rtrace, stages[2][2])
        elif seed == "extra-collective" and T == hlo_T:
            # inject a rogue replicating all_gather after the return
            def rtrace(*a, _rf=stages[2][1]):
                out = _rf(*a)
                gather = jax.jit(shard_map(
                    lambda y: jax.lax.all_gather(y, "shard", axis=0,
                                                 tiled=True),
                    mesh=mesh, in_specs=(P("shard"),), out_specs=P(),
                    check_vma=False))
                return out + (gather(out[0]),)
            stages[2] = ("query_return", rtrace, stages[2][2])

        for phase, fn, fargs in [("insert", ifn, iargs),
                                 ("delete", dfn, dargs)] + stages:
            cj = jax.make_jaxpr(fn)(*fargs)
            rep = jaxpr_pass.analyze_phase(cj, phase, T, contracts)
            phases[phase][str(T)] = rep
            eqns[phase][T] = rep["eqns"]
            violations.extend(rep["violations"])

        if T == hlo_T:
            hlo_ctx = {"idx": idx, "ifn": ifn, "iargs": iargs}

    ratio = manifest.flatness_ratio(contracts)
    flat_report: Dict[str, Any] = {"max_ratio": ratio, "eqns": {}}
    for phase, by_T in eqns.items():
        flat_report["eqns"][phase] = {str(t): n for t, n in by_T.items()}
        flat = jaxpr_pass.check_flatness(by_T, ratio, phase)
        violations.extend(flat)
    report["jaxpr"] = {"phases": phases, "flatness": flat_report}

    # ---- HLO / memory pass on the compiled executables at T=hlo_T ----
    # the query steps as the front ends run them: dispatch donates the
    # staging buffer; scan/return always donate the routed payloads
    executables = [("insert",
                    hlo_ctx["ifn"].lower(*hlo_ctx["iargs"]).compile())]
    for phase, fn, fargs in query_stages(
            hlo_ctx["idx"], queries, K, donate=seed != "broken-donation"):
        executables.append((phase, fn.lower(*fargs).compile()))

    hlo_report: Dict[str, Any] = {"n_tables": hlo_T, "donation": {},
                                  "memory": {}, "collectives": {}}
    for phase, compiled in executables:
        text = compiled.as_text()
        don = hlo_pass.donation_report(text, phase, contracts)
        mem = hlo_pass.memory_report(compiled, phase, contracts)
        col = hlo_pass.hlo_collective_report(text, phase, contracts)
        hlo_report["donation"][phase] = don
        hlo_report["memory"][phase] = mem
        hlo_report["collectives"][phase] = col
        for sub in (don, mem, col):
            violations.extend(sub["violations"])

    vmem = hlo_pass.vmem_report(contracts)
    hlo_report["vmem"] = vmem
    violations.extend(vmem["violations"])
    report["hlo"] = hlo_report
    return violations


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.check",
        description="Static SPMD contract gate (jaxpr + HLO/memory + "
                    "repolint) against src/repro/analysis/contracts.json.")
    ap.add_argument("--json", dest="json_out", default="contracts_report.json",
                    help="report path (default: %(default)s)")
    ap.add_argument("--repo-root", default=None,
                    help="repo root for the lint pass (default: inferred)")
    ap.add_argument("--seed-violation", choices=SEEDABLE, default=None,
                    help="inject one violation of the given class "
                         "(self-test that the gate actually fails)")
    ap.add_argument("--skip-compile", action="store_true",
                    help="repolint + VMEM only (no jax tracing)")
    args = ap.parse_args(argv)

    contracts = manifest.load_contracts()
    root = args.repo_root or manifest.repo_root()

    # must precede any jax import anywhere in this process
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count="
        f"{contracts['check_config']['n_shards']} "
        + os.environ.get("XLA_FLAGS", ""))
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    report: Dict[str, Any] = {
        "schema": 1,
        "contracts": manifest.CONTRACTS_PATH,
        "check_config": contracts["check_config"],
        "seed_violation": args.seed_violation,
    }
    violations: List[str] = []

    lint = _run_repolint(contracts, root, args.seed_violation)
    report["repolint"] = lint
    violations.extend(f"repolint: {v['path']}:{v['line']}: [{v['rule']}] "
                      f"{v['msg']}" for v in lint["violations"])

    if args.skip_compile:
        from repro.analysis import hlo_pass  # jax-free entry points only
        vmem = hlo_pass.vmem_report(contracts)
        report["vmem_only"] = vmem
        violations.extend(vmem["violations"])
    else:
        violations.extend(
            _run_compiled_passes(contracts, args.seed_violation, report))

    report["violations"] = violations
    report["ok"] = not violations
    with open(args.json_out, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)

    if violations:
        print(f"CONTRACT VIOLATIONS ({len(violations)}):")
        for v in violations:
            print(f"  - {v}")
    else:
        jx = report.get("jaxpr", {}).get("phases", {})
        for phase in ("insert", "delete",
                      "query_dispatch", "query_scan", "query_return"):
            for t, rep in sorted(jx.get(phase, {}).items()):
                coll = rep["collectives"] or "{}"
                print(f"  ok {phase:6s} T={t}: {rep['eqns']:4d} eqns, "
                      f"collectives {coll}")
        print(f"  ok repolint: {lint['files_scanned']} files clean")
        print("all contracts hold")
    print(f"report: {args.json_out}")
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
