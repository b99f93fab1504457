#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, their configurations, traffic mixes and metrics are listed in
``BENCHMARK.json`` at the root of the checkout; ``bench/harness.py`` says
what a run does.  The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and ``checks`` last: each number the
correctness comparison read, beside its limit).  The checks are also the
last lines of standard error.

Without a TPU, or with fewer chips than the cell needs, it exits with
code 3 and prints no result.  JAX's persistent compilation cache is kept
at ``.jax_cache/`` in the checkout, so only a cell's first run compiles.
"""
import os
import time


def _process_start() -> float:
    """Process start on the perf_counter clock (from /proc when it can)."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - start_ticks / os.sysconf("SC_CLK_TCK"))
        return now - max(age, 0.0)
    except (OSError, ValueError, IndexError):
        return now


PROCESS_START = _process_start()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.compile_cache import enable_compile_cache
    from bench import harness

    enable_compile_cache()
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace),
                                  process_start=PROCESS_START)
    except harness.NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        ok = c["value"] <= c["limit"]
        print(f"check {name} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
