"""Mean wait of the window's steady-state batches (``_batches.steady``:
those with no query submitted before the window's first answer) from
the end of their host-side enqueue (the pipeline's ``lsh.enqueue`` span,
whose end is ``BatchRecord.t_enqueued``) to the start of their
``dispatch_shard`` program on the device; a program that started before
the enqueue ended waited 0.

The record's times are on the service's clock, which the harness leaves
at its default, ``time.monotonic``.  They are put on the trace's clock
through the window's start, which the harness reads on
``time.perf_counter`` (``run.t0``) as it opens the ``bench.window`` span;
on Linux both clocks read ``CLOCK_MONOTONIC``.  Where the two clocks
differ, or a record's enqueue falls outside the window, the reader says
so on standard error and reads nothing.

Batches pair with programs by order: the window's k-th batch with its
k-th ``dispatch_shard`` program (the pipeline enqueues the programs in
submit order, and the warm-up's ran before the window opened); a window
with a different number of each reads nothing.  The trace's device and
host times agree to within about a millisecond (one v5e: a program began
0.13 ms before its batch's enqueue span did), so a reading under a
millisecond is within that error.
"""
import sys
import time

from bench import trace
from bench.metrics._batches import records, steady


def _skip(why):
    print(f"device_queue_ms: {why}; not read", file=sys.stderr)


def read(run):
    recs, keep = records(run), {r.seq for r in steady(run)}
    if not run.events or not keep:
        return None
    if (time.get_clock_info("monotonic").implementation
            != time.get_clock_info("perf_counter").implementation):
        return _skip("the service's clock is not the harness's")
    w0, w1 = trace.window(run.events)
    on_trace = lambda t: w0 + (t - run.t0) * 1e9
    if not all(w0 - 1e6 <= on_trace(r.t_enqueue) <= w1 for r in recs):
        return _skip("a batch left admission outside the window")
    starts = sorted(e["start_ns"] for e in
                    trace.modules(run.events, "dispatch_shard", w0, w1))
    if len(starts) != len(recs):
        return _skip(f"{len(starts)} dispatch programs for "
                     f"{len(recs)} batches")
    waits = {r.seq: max(0.0, s - on_trace(r.t_enqueued)) / 1e6
             for r, s in zip(recs, starts)}
    return sum(waits[s] for s in keep) / len(keep)
