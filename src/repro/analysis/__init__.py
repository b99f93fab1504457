"""Static SPMD contract analysis.

This package is the repo's enforcement substrate for the paper's central
claim — a *statically provable* network bound.  The distributed index
promises one fused collective per phase (insert: 1 all_to_all, query:
1 in its dispatch step + 1 in its routed-return step = 2, delete: 0),
a jaxpr that stays flat as the table count T grows, no O(R*N)
intermediates, donated store buffers that the compiled executable
actually aliases, and a hot path free of host syncs.  Those invariants
live declaratively in ``contracts.json`` and are verified structurally
(primitive identity, never text regex) by three passes:

- :mod:`repro.analysis.jaxpr_pass` — ClosedJaxpr walk: collective
  counts, equation counts / flatness in T, intermediate-size ceilings,
  64-bit dtype drift.
- :mod:`repro.analysis.hlo_pass` — compiled-executable checks: donation
  aliasing, ``memory_analysis()`` temp-byte budgets, Pallas VMEM
  budgets, HLO collective counts.
- :mod:`repro.analysis.repolint` — AST lint for repo-specific rules
  ruff can't express (host syncs in hot paths, deprecated shims,
  positional kernel-API calls, StoreState mutation outside its owners).

Run the whole gate with ``python -m repro.analysis.check``.  Only
:mod:`manifest` and :mod:`repolint` are import-safe without jax; the
other passes import jax lazily so ``check`` can configure XLA host
devices first.
"""

from repro.analysis.manifest import CONTRACTS_PATH, load_contracts, repo_root

__all__ = ["CONTRACTS_PATH", "load_contracts", "repo_root"]
