"""Share of the expanded probe rows the window's CSR scan steps built
(padding included) whose probe names a non-empty bucket span
(``BatchRecord.probes_live / probes_expanded``, summed over steps)."""
import sys

from bench.metrics._batches import records


def read(run):
    recs = records(run)
    expanded = sum(r.probes_expanded for r in recs)
    if not expanded:
        return None
    live = sum(r.probes_live for r in recs)
    print(f"probe_live_share: {live} live of {expanded} expanded probe "
          f"rows over {len(recs)} steps", file=sys.stderr)
    return live / expanded
