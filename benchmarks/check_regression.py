"""CI benchmark-regression gate.

Compares a fresh ``benchmarks.run --smoke --json`` artifact against the
committed ``benchmarks/baseline_ci.json``:

  PYTHONPATH=src python -m benchmarks.check_regression bench.json \
      --baseline benchmarks/baseline_ci.json --threshold 1.5 \
      --contracts contracts_report.json

A bench FAILS when its wall time exceeds threshold x baseline.  The
threshold is deliberately generous (default 1.5x): shared CI runners are
noisy, and the gate exists to catch real order-of-magnitude regressions
(a retrace per step, an accidental O(R*N) materialisation), not 10%
jitter.  Benches new in the current run pass with a note (refresh the
baseline to start tracking them); benches that vanished fail, since a
silently-dropped bench would hide a regression forever.

Structural metrics recorded by the tables sweep at T in {1, 2, 4} are
deterministic (no runner noise) and gated tighter:

- ``jaxpr_eqns_*`` (analyzer equation counts) at the manifest's
  flatness ratio from ``src/repro/analysis/contracts.json`` -- growth
  there means a per-table Python loop reappearing in a hot path, which
  wall time on a tiny smoke config would hide;
- ``collectives_*`` (fused all_to_all counts per phase) EXACTLY -- the
  paper's whole result is the O(1)-collectives bound.

The gate also requires the SPMD contract report written by
``python -m repro.analysis.check --json``: a missing or failing report
fails the gate (the same vanish policy as benches -- a silently-skipped
analyzer hides exactly the regressions it exists to catch).  Pass
``--contracts ''`` to explicitly skip for local timing-only runs.

To refresh after an intentional change:
  PYTHONPATH=src python -m benchmarks.run --smoke --json \
      benchmarks/baseline_ci.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from repro.analysis.manifest import flatness_ratio

# guards the ratio against meaninglessly tiny baselines (timer noise)
MIN_BASELINE_S = 0.05

# deterministic structural metrics: (prefix -> gate kind)
RATIO_METRICS = ("jaxpr_eqns", "jaxpr_lines")  # lines: legacy baselines
EXACT_METRICS = ("collectives_",)

# trace size is deterministic, so the gate is much tighter than wall
# time; single source of truth is the contract manifest
JAXPR_THRESHOLD = flatness_ratio()


def _gated_metrics(*sources: dict) -> list[str]:
    prefixes = RATIO_METRICS + EXACT_METRICS
    return sorted({k for src in sources for k in src
                   if k.startswith(prefixes)})


def compare(current: dict, baseline: dict, threshold: float) -> list[str]:
    """Returns a list of failure messages (empty = gate passes)."""
    failures = []
    cur, base = current["benches"], baseline["benches"]
    print(f"{'bench':<28} {'base_s':>8} {'cur_s':>8} {'ratio':>6}  verdict")
    for name in sorted(set(base) | set(cur)):
        if name not in cur:
            failures.append(f"{name}: present in baseline but not run")
            print(f"{name:<28} {base[name]['wall_s']:>8.2f} {'--':>8} "
                  f"{'--':>6}  MISSING")
            continue
        if name not in base:
            print(f"{name:<28} {'--':>8} {cur[name]['wall_s']:>8.2f} "
                  f"{'--':>6}  new (not gated)")
            continue
        b = max(base[name]["wall_s"], MIN_BASELINE_S)
        c = cur[name]["wall_s"]
        ratio = c / b
        ok = ratio <= threshold
        print(f"{name:<28} {b:>8.2f} {c:>8.2f} {ratio:>6.2f}  "
              f"{'ok' if ok else 'REGRESSION'}")
        if not ok:
            failures.append(
                f"{name}: {c:.2f}s vs baseline {b:.2f}s "
                f"({ratio:.2f}x > {threshold}x)")
        # deterministic structural metrics: compiled trace size must stay
        # flat and collective counts must not move at all.  Same vanish
        # policy as whole benches: a gated metric that stops being
        # recorded FAILS -- a silently-dropped gate hides exactly the
        # structural regression it exists to catch.
        for metric in _gated_metrics(base[name], cur[name]):
            label = f"{name}.{metric}"
            if metric not in cur[name]:
                failures.append(
                    f"{label}: present in baseline but not recorded")
                print(f"{label:<28} {base[name][metric]:>8d} {'--':>8} "
                      f"{'--':>6}  MISSING")
                continue
            if metric not in base[name]:
                print(f"{label:<28} {'--':>8} {cur[name][metric]:>8d} "
                      f"{'--':>6}  new (not gated)")
                continue
            mb, mc = base[name][metric], cur[name][metric]
            if metric.startswith(EXACT_METRICS):
                mok = mc == mb
                print(f"{label:<28} {mb:>8d} {mc:>8d} {'--':>6}  "
                      f"{'ok' if mok else 'REGRESSION'}")
                if not mok:
                    failures.append(
                        f"{label}: {mc} collectives vs baseline {mb} "
                        f"(exact-match gate; the O(1)-collective bound "
                        f"moved)")
                continue
            mb = max(mb, 1)
            mratio = mc / mb
            mok = mratio <= JAXPR_THRESHOLD
            print(f"{label:<28} {mb:>8d} {mc:>8d} "
                  f"{mratio:>6.2f}  {'ok' if mok else 'REGRESSION'}")
            if not mok:
                failures.append(
                    f"{label}: {mc} eqns vs baseline {mb} "
                    f"({mratio:.2f}x > {JAXPR_THRESHOLD}x)")
    return failures


def check_contract_report(path: str) -> list[str]:
    """Loud-failure check of the analyzer's JSON report artifact."""
    if not path:
        print("contract report check SKIPPED (--contracts '')")
        return []
    if not os.path.exists(path):
        return [f"contract report {path!r} missing -- generate it with: "
                f"PYTHONPATH=src python -m repro.analysis.check "
                f"--json {path}"]
    with open(path) as f:
        report = json.load(f)
    failures = []
    if not report.get("ok", False):
        viol = report.get("violations", ["<no violations recorded>"])
        failures.append(
            f"contract report {path}: ok=false "
            f"({len(viol)} violation(s); first: {viol[0]})")
    phases = report.get("jaxpr", {}).get("phases", {})
    for phase in ("insert", "delete",
                  "query_dispatch", "query_scan", "query_return"):
        reps = phases.get(phase)
        if not reps:
            failures.append(
                f"contract report {path}: jaxpr metrics for phase "
                f"{phase!r} vanished (analyzer silently degraded?)")
            continue
        for t, rep in reps.items():
            if "collectives" not in rep or "eqns" not in rep:
                failures.append(
                    f"contract report {path}: {phase}[T={t}] lost its "
                    f"gated collectives/eqns metrics")
    if not failures:
        n = len(report.get("repolint", {}).get("violations", []))
        print(f"contract report ok ({path}; repolint violations: {n})")
    return failures


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("current", help="fresh --json artifact")
    ap.add_argument("--baseline", default="benchmarks/baseline_ci.json")
    ap.add_argument("--threshold", type=float, default=1.5)
    ap.add_argument("--contracts", default="contracts_report.json",
                    help="SPMD contract report from repro.analysis.check; "
                         "a missing/failing report FAILS the gate "
                         "(pass '' to skip explicitly)")
    args = ap.parse_args(argv)
    with open(args.current) as f:
        current = json.load(f)
    with open(args.baseline) as f:
        baseline = json.load(f)
    failures = check_contract_report(args.contracts)
    failures += compare(current, baseline, args.threshold)
    if failures:
        print("\nbenchmark gate FAILED:")
        for msg in failures:
            print("  -", msg)
        print("(intentional change? refresh with: PYTHONPATH=src python -m"
              " benchmarks.run --smoke --json benchmarks/baseline_ci.json)")
        sys.exit(1)
    print("\nbenchmark gate passed")


if __name__ == "__main__":
    main()
