#!/usr/bin/env python3
"""The controls of the correctness comparison: each has to come out false.

A control is the plain reference put in the program's place and computed
below the precision the configuration states (float32 at HIGHEST):

  high            -- hashing and distances in three bfloat16 passes
                     ("high"), the next lower precision;
  high-distances  -- hashing at HIGHEST and only the distances at "high",
                     the step a faster distance product in the kernel
                     would take.

For each seed it draws the cell's queries as a run of that seed would
(the mix's set-up writes, then ``--queries`` queries, rounded up to
whole buckets of the mix's size), answers them with each control, and
holds the answers against the reference with the benchmark's own
comparison (``bench/check.py``).  It prints each reading beside its
limit and, last, one JSON line.  The benchmark's runs do not run it.

  python bench/control.py --workload random1m.search --seeds 11 12 13
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# name -> (distance precision, hashing precision).  "float32" is no
# control: it stands in for a sound program (float32 at HIGHEST, distances
# as the product gives them) and has to come out correct.
CONTROLS = {"high": ("high", "high"),
            "high-distances": ("high", "highest"),
            "float32": ("highest", "highest")}


def readings(workload: str, seeds, n_queries: int, controls=("high",),
             overrides=None) -> list[dict]:
    """The comparison's numbers of each control against "highest"."""
    from bench import check
    from bench.data import Dataset
    from bench.harness import find_cell, load_json, merged
    from bench.reference.lsh import LSHSpec, Reference
    from bench.traffic import Traffic, load_mix

    over = overrides or {}
    cell, cfg_entry = find_cell(load_json(os.path.join(ROOT,
                                                       "BENCHMARK.json")),
                                workload)
    config = merged(load_json(os.path.join(ROOT, cfg_entry["file"])),
                    over.get("config"))
    mix = merged(load_mix(cell["traffic"]), over.get("traffic"))
    spec = LSHSpec.from_config(config)
    n = int(config["points"])
    extra = int(mix.get("setup", {}).get("insert", 0))
    rows = Dataset(config["dataset"]).rows(n + extra)
    ref = Reference(spec, rows)
    ctls = {c: Reference(spec, rows, precision=CONTROLS[c][0],
                         hash_precision=CONTROLS[c][1], exact=False)
            for c in controls}
    out = []
    for seed in seeds:
        traffic = Traffic(mix, spec.d, spec.r, seed)
        live = np.ones((n + extra,), bool)
        if extra:
            live[traffic.setup_deletes(n, np.arange(n, n + extra))] = False
        traffic.set_live(live, n)
        q = np.concatenate([traffic.plant(rows)[0]
                            for _ in range(-(-n_queries // traffic.bucket))])
        qids = (np.arange(len(q)) % traffic.bucket).astype(np.int32)
        rd, rg = ref.topk(q, qids, live)
        for name, ctl in ctls.items():
            t = time.perf_counter()
            cd, cg = ctl.topk(q, qids, live)
            inr = (cg >= 0) & (cg < len(live))
            deleted = int((inr & ~live[np.where(inr, cg, 0)]).sum())
            v = check.compare(cd, cg, ref.dists(q, cg), rd, rg, deleted, 0,
                              config["limits"])
            v.update(seed=seed, control=name,
                     seconds=time.perf_counter() - t)
            out.append(v)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--queries", type=int, default=1024)
    ap.add_argument("--controls", nargs="+", choices=sorted(CONTROLS),
                    default=["high", "high-distances"])
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    import jax
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind}",
          file=sys.stderr)
    res = readings(args.workload, args.seeds, args.queries, args.controls)
    for r in res:
        print(f"control {r['control']} seed {r['seed']}: "
              f"correct={r['correct']} "
              + " ".join(f"{k}={c['value']!r}(limit {c['limit']!r})"
                         for k, c in r["checks"].items())
              + f" in {r['seconds']:.1f}s", file=sys.stderr, flush=True)
    print(json.dumps({"workload": args.workload, "platform": dev.platform,
                      "kind": dev.device_kind,
                      "control": [{"control": r["control"],
                                   "seed": r["seed"],
                                   "correct": r["correct"],
                                   "checks": {k: {"value": min(c["value"], 1e30),
                                                  "limit": c["limit"]}
                                              for k, c in r["checks"].items()}}
                                  for r in res]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
