"""Mean device time of the query-scan step (``scan_shard``) per batch in
the traced window, from the device trace."""
from bench import trace


def read(run):
    if not run.events:
        return None
    w0, w1 = trace.window(run.events)
    steps = trace.modules(run.events, "scan_shard", w0, w1)
    return sum(e["dur_ns"] for e in steps) / len(steps) / 1e6 if steps \
        else None
