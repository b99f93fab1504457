"""Percent of the traced window in which no operation ran on the device
(1 minus the busy union, averaged over the chips)."""
from bench import trace


def read(run):
    if not run.events:
        return None
    w0, w1 = trace.window(run.events)
    return 100.0 * (1.0 - trace.busy_ns(run.events, w0, w1) / (w1 - w0))
