"""95th percentile (nearest rank) of submit-to-answer time over every
query admitted in the window; a failed query counts as infinitely late."""
from bench.metrics._stats import percentile


def read(run):
    return percentile(run.latencies_ms(), 95)
