"""The query scan's share of its roofline: the least time the chip needs
for the scan's work (bench/roofline.py: distinct probed buckets' live
rows read once, 2*d flops per query-candidate pair) over the scan step's
device time, summed over the window's batches."""
import sys

from bench import roofline, trace


def read(run):
    if not run.events:
        return None
    w0, w1 = trace.window(run.events)
    steps = trace.modules(run.events, "scan_shard", w0, w1)
    work = run.scan_work()
    if not steps or len(steps) != len(work):
        print(f"scan_roofline: {len(steps)} traced scan steps for "
              f"{len(work)} batches; not read", file=sys.stderr)
        return None
    least, bound = roofline.least_seconds(work, roofline.peaks(
        run.device_kind))
    print(f"scan_roofline: least time {least:.6f}s, bound by {bound}",
          file=sys.stderr)
    return 100.0 * least / (sum(e["dur_ns"] for e in steps) / 1e9)
