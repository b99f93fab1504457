"""HLO / compiled-executable contract pass.

Extends the descriptive parsers in ``repro.launch.hlo_analysis`` /
``hlo_cost`` into a *gating* layer over the actually-compiled program:

- **Donation**: donated buffers must be honored by XLA.  Insert donates
  the six store columns and XLA aliases them output<-input
  (``input_output_alias`` in the module header).  The query
  dispatch's buffer under ``donate=True`` has no shape-matching output,
  so XLA records it as a ``buffer_donor`` instead — both forms count as
  honored; a donation that appears in neither was silently copied.
- **Memory**: ``compiled.memory_analysis()`` temp bytes vs budget.
- **VMEM**: the Pallas kernels' declared per-step VMEM footprint
  (``vmem_bytes_per_step``) vs budget, evaluated at the *maximum*
  supported dims so the envelope is bounded, not one sample point.
- **Collectives**: HLO-level collective counts (via
  ``hlo_analysis.collective_bytes``) cross-checking the jaxpr budgets
  on the compiled artifact.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Set

_PARAM_IDX = re.compile(r"\(\s*(\d+)\s*,")


def _header_block(hlo_text: str, attr: str) -> str:
    """Extract the balanced-brace value of ``attr={...}`` from the module
    header (entries like ``{0}: (3, {}, may-alias)`` nest braces, so a
    non-greedy regex would stop at the first inner ``}``)."""
    marker = attr + "={"
    start = hlo_text.find(marker)
    if start < 0:
        return ""
    i, depth = start + len(marker), 1
    while i < len(hlo_text) and depth:
        depth += {"{": 1, "}": -1}.get(hlo_text[i], 0)
        i += 1
    return hlo_text[start + len(marker):i - 1]


def aliased_params(hlo_text: str) -> Set[int]:
    """Parameter indices aliased to an output in the module header."""
    return {int(i) for i in
            _PARAM_IDX.findall(_header_block(hlo_text, "input_output_alias"))}


def donor_params(hlo_text: str) -> Set[int]:
    """Parameter indices registered as donatable buffers (donated but
    not aliased to a specific output)."""
    return {int(i) for i in
            _PARAM_IDX.findall(_header_block(hlo_text, "buffer_donor"))}


def donation_report(hlo_text: str, phase: str,
                    contracts: Dict[str, Any]) -> Dict[str, Any]:
    """Check that donation was honored in the compiled executable.

    Per-phase budgets are named ``{phase}_min_aliased_params`` (params
    that must be aliased output<-input) and ``{phase}_min_donated_params``
    (params that must at least be aliased OR registered as buffer
    donors); a phase with neither key has no donation contract.
    """
    budget = contracts["hlo"]["donation"]
    aliased = aliased_params(hlo_text)
    donors = donor_params(hlo_text)
    honored = aliased | donors
    violations: List[str] = []
    want_aliased = budget.get(f"{phase}_min_aliased_params")
    if want_aliased is not None and len(aliased) < int(want_aliased):
        violations.append(
            f"{phase}: only {len(aliased)} donated params aliased in the "
            f"executable (contract requires >= {int(want_aliased)}); "
            f"donated buffers are being copied, not reused")
    want_donated = budget.get(f"{phase}_min_donated_params")
    if want_donated is not None and len(honored) < int(want_donated):
        violations.append(
            f"{phase}: only {len(honored)} input buffers aliased or "
            f"registered as donors (contract requires >= "
            f"{int(want_donated)}); the donated buffer is silently "
            f"copied every step")
    return {
        "phase": phase,
        "aliased_params": sorted(aliased),
        "donor_params": sorted(donors),
        "violations": violations,
    }


def memory_report(compiled, phase: str,
                  contracts: Dict[str, Any]) -> Dict[str, Any]:
    """Gate compiled temp bytes against the per-phase budget."""
    ceiling = int(contracts["hlo"]["temp_bytes_ceiling"][phase])
    report: Dict[str, Any] = {"phase": phase, "temp_bytes_ceiling": ceiling,
                              "violations": []}
    try:
        stats = compiled.memory_analysis()
        temp = int(stats.temp_size_in_bytes)
    except Exception as exc:  # backend without memory_analysis support
        report["note"] = f"memory_analysis unavailable: {exc!r}"
        return report
    report.update(
        temp_bytes=temp,
        argument_bytes=int(getattr(stats, "argument_size_in_bytes", 0)),
        output_bytes=int(getattr(stats, "output_size_in_bytes", 0)),
        alias_bytes=int(getattr(stats, "alias_size_in_bytes", 0)),
    )
    if temp > ceiling:
        report["violations"].append(
            f"{phase}: compiled temp memory {temp} bytes exceeds budget "
            f"{ceiling} (possible O(R*N) scratch materialization)")
    return report


def hlo_collective_report(hlo_text: str, phase: str,
                          contracts: Dict[str, Any]) -> Dict[str, Any]:
    """Exact-match HLO collective counts against the manifest."""
    from repro.launch.hlo_analysis import collective_bytes
    info = collective_bytes(hlo_text)
    counts = {k: int(v) for k, v in info["counts"].items()}
    budget = {k: int(v) for k, v in
              contracts["hlo"]["collectives"].get(phase, {}).items()
              if not k.startswith("_")}
    violations = []
    for kind in sorted(set(counts) | set(budget)):
        want, got = budget.get(kind, 0), counts.get(kind, 0)
        if got != want:
            violations.append(
                f"{phase}: HLO has {got} {kind} ops, contract allows "
                f"exactly {want}")
    return {"phase": phase, "counts": counts,
            "collective_bytes": int(info.get("total_bytes", 0)),
            "violations": violations}


def vmem_report(contracts: Dict[str, Any]) -> Dict[str, Any]:
    """Bound the Pallas kernels' declared VMEM per step at the envelope
    maxima from the manifest."""
    from repro.kernels.bucket_search import (gather_vmem_bytes_per_step,
                                             vmem_bytes_per_step)
    vc = contracts["vmem"]
    budget = int(vc["budget_bytes"])
    d, L, K = int(vc["d_max"]), int(vc["L_max"]), int(vc["k_neighbors_max"])
    scan = int(vmem_bytes_per_step(d, L, K))
    gather = int(gather_vmem_bytes_per_step(d, K))
    violations = []
    for name, got in (("bucket_search", scan), ("bucket_gather", gather)):
        if got > budget:
            violations.append(
                f"vmem: {name} kernel declares {got} bytes/step at "
                f"d={d}, L={L}, K={K} > budget {budget}")
    return {"budget_bytes": budget, "envelope": {"d": d, "L": L, "K": K},
            "bucket_search_bytes": scan, "bucket_gather_bytes": gather,
            "violations": violations}
