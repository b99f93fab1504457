"""Contract manifest loader (jax-free; safe to import before XLA init).

The manifest — ``contracts.json`` next to this module — is the single
committed source of truth for every budget the analyzer gates on:
per-phase collective counts, jaxpr flatness ratio, intermediate-size
ceilings, donation/temp-byte/VMEM budgets, and repolint allowlists.
Changing a budget means editing the manifest in the same PR, which makes
the change visible in the diff.
"""

from __future__ import annotations

import json
import os

_DIR = os.path.dirname(os.path.abspath(__file__))
CONTRACTS_PATH = os.path.join(_DIR, "contracts.json")

_REQUIRED_TOP = ("check_config", "jaxpr", "hlo", "vmem", "repolint")
_REQUIRED_JAXPR = ("collectives", "flatness", "max_intermediate_numel_per_table")


def load_contracts(path: str | None = None) -> dict:
    """Load and structurally validate the contract manifest."""
    path = path or CONTRACTS_PATH
    with open(path) as f:
        doc = json.load(f)
    if doc.get("schema") != 1:
        raise ValueError(f"{path}: unsupported contract schema {doc.get('schema')!r}")
    missing = [k for k in _REQUIRED_TOP if k not in doc]
    if missing:
        raise ValueError(f"{path}: missing contract sections {missing}")
    missing = [k for k in _REQUIRED_JAXPR if k not in doc["jaxpr"]]
    if missing:
        raise ValueError(f"{path}: missing jaxpr contract keys {missing}")
    for phase in ("insert", "delete",
                  "query_dispatch", "query_scan", "query_return"):
        if phase not in doc["jaxpr"]["collectives"]:
            raise ValueError(f"{path}: no collective budget for phase {phase!r}")
    ratio = doc["jaxpr"]["flatness"]["max_ratio"]
    if not (1.0 <= float(ratio) < 2.0):
        raise ValueError(f"{path}: implausible flatness max_ratio {ratio}")
    return doc


def repo_root() -> str:
    """Repository root, assuming the canonical src/repro/analysis layout."""
    return os.path.dirname(os.path.dirname(os.path.dirname(_DIR)))


def flatness_ratio(doc: dict | None = None) -> float:
    """The single jaxpr-flatness ceiling (shared with check_regression)."""
    doc = doc or load_contracts()
    return float(doc["jaxpr"]["flatness"]["max_ratio"])
