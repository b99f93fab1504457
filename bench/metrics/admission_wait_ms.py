"""Mean wait of the window's steady-state queries (``_batches.steady``:
the batches with no query submitted before the window's first answer)
from their submit to the moment their batch left admission for the
pipeline (``BatchRecord``: ``admission_wait_s`` summed over the batch's
queries, over ``take``; both ends on the service's clock)."""
from bench.metrics._batches import steady


def read(run):
    recs = steady(run)
    n = sum(r.take for r in recs)
    if not n:
        return None
    return 1e3 * sum(r.admission_wait_s for r in recs) / n
