"""Share of the window's query-scan steps in which some shard's overflow
guard took the full scan, as the program counted it (each batch's
``BatchRecord.fallback``)."""
from bench.metrics._batches import records


def read(run):
    recs = records(run)
    if not recs:
        return None
    return sum(r.fallback for r in recs) / len(recs)
