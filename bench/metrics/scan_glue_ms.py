"""Mean device time per query-scan step (``scan_shard``) in operations
other than the Pallas kernels (``bucket_gather_pallas*``,
``bucket_search_pallas*``), control-flow containers left out as in the
breakdown's ``device_ops``: span search, probe expansion and sort, pad
copies, norms and merges.  Overlapping operations count once."""
from bench import trace

KERNELS = ("bucket_gather_pallas", "bucket_search_pallas")


def read(run):
    if not run.events:
        return None
    w0, w1 = trace.window(run.events)
    steps = trace.modules(run.events, "scan_shard", w0, w1)
    if not steps:
        return None
    skip = KERNELS + trace._CONTAINERS
    glue = sum(trace.union_ns(
        [(op["start_ns"], op["start_ns"] + op["dur_ns"])
         for op in trace.ops_inside(run.events, s)
         if not trace.op_name(op["name"]).startswith(skip)])
        for s in steps)
    return glue / len(steps) / 1e6
