"""Share of traced query-scan steps in which the CSR gather kernel did not
run: the step's overflow guard (a ``lax.cond``) took the full scan."""
from bench import trace

GATHER = "bucket_gather_pallas"   # the kernel's instruction name


def read(run):
    if not run.events:
        return None
    w0, w1 = trace.window(run.events)
    steps = trace.modules(run.events, "scan_shard", w0, w1)
    if not steps:
        return None
    missed = sum(not any(trace.op_name(op["name"]).startswith(GATHER)
                         for op in trace.ops_inside(run.events, s))
                 for s in steps)
    return missed / len(steps)
