"""``scan_us_per_tile`` on small runs built by hand, and on the run
recorded on one v5e (``data/search_trace_spans.json``, made by a program
that did not count grid steps yet)."""
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from bench import trace
from bench.metrics import scan_us_per_tile
from bench.tests.test_program_metrics import HERE, run_of
from bench.tests.test_trace import D0, ev
from repro.serving import BatchRecord

GATHER = ("%bucket_gather_pallas.1 = (f32[1024,128]) custom-call(), "
          'custom_call_target="tpu_custom_call"')
TAIL = "%bucket_search_pallas_tail.2 = (f32[128,128]) custom-call()"
FALLBACK = "%bucket_search_pallas_fallback.3 = (f32[128,128]) custom-call()"


def rec(seq, grid_steps, *, hits=500, filled=400, take=64, columns=8):
    counters = [1, 9, 8, 30, 1024, hits, filled, grid_steps][:columns]
    return BatchRecord(
        seq=seq, reason="full", take=take, pad=0, drops=0, routed_rows=take,
        admission_wait_s=0.0, t_enqueue=0.0, t_enqueued=0.0,
        counters=np.array([counters], np.int32))


def scan(start, dur):
    return ev(D0, trace.MODULES_LINE, "jit_scan_shard(1)", start, dur)


def op(name, start, dur):
    return ev(D0, trace.OPS_LINE, name, start, dur)


EVENTS = [
    ev("/host:CPU", "python", "bench.window", 100, 10_000),
    # batch 0 (before the window's first answer): a full scan
    scan(200, 2_000), op(FALLBACK, 300, 1_000),
    # batch 1: the gather and the tail scan, overlapping by 100 ns, and
    # glue that is not a kernel
    scan(3_000, 2_000), op("%fusion.7 = f32[4] fusion()", 3_000, 400),
    op(GATHER, 3_400, 600), op(TAIL, 3_900, 500),
    # batch 2: a full scan
    scan(6_000, 2_000), op(FALLBACK, 6_100, 1_600),
]


def test_kernel_time_over_grid_steps_of_the_steady_state():
    run = run_of([rec(0, 10), rec(1, 25), rec(2, 40)], EVENTS)
    # batch 0 fills the closed loop and is left out
    want = ((600 + 500 - 100) / 25 + 1_600 / 40) / 2 / 1e3
    assert scan_us_per_tile.read(run) == pytest.approx(want)


def test_reads_nothing_without_grid_steps_or_pairs():
    recs = [rec(0, 10), rec(1, 25), rec(2, 40)]
    # a program from before the count: records of five columns
    old = [rec(i, 10, columns=5) for i in range(3)]
    assert old[0].grid_steps == 0
    assert scan_us_per_tile.read(run_of(old, EVENTS)) is None
    # the jnp oracle runs no kernel
    assert scan_us_per_tile.read(run_of(
        [rec(0, 10), rec(1, 0), rec(2, 40)], EVENTS)) is None
    # a batch with no scan program to pair, and no trace
    assert scan_us_per_tile.read(run_of(recs + [rec(3, 40)], EVENTS)) is None
    assert scan_us_per_tile.read(run_of(recs, None)) is None


def test_prints_hits_and_filled_ranks(capsys):
    run = run_of([rec(0, 10, hits=640, filled=6_400),
                  rec(1, 25, hits=1_280, filled=3_200),
                  rec(2, 40, hits=0, filled=0)], EVENTS)
    run.reference = SimpleNamespace(spec=SimpleNamespace(K=100))
    scan_us_per_tile.read(run)
    err = capsys.readouterr().err
    assert "hits/query 10.00" in err                 # 1,920 over 192
    assert "filled/(queries*K) 0.50000" in err       # 9,600 of 19,200


def test_on_the_recorded_run():
    """Each recorded scan step spent 4,297.0 ms in the full-scan kernel,
    over the 24 row tiles x 31,250 store tiles of that program's step
    (2,955 received rows, a 4M-slot store): 5.73 us a grid step.  Its
    records count no grid steps, so the reader reads nothing there."""
    with open(os.path.join(HERE, "data", "search_trace_spans.json")) as f:
        d = json.load(f)
    w0, w1 = trace.window(d["events"])
    steps = trace.modules(d["events"], "scan_shard", w0, w1)
    ns = [scan_us_per_tile.kernel_ns(d["events"], s) for s in steps]
    assert ns == [4297003213.0, 4297040582.0]
    assert ns[0] / (24 * 31_250) / 1e3 == pytest.approx(5.7293, abs=1e-4)
    recs = {r["seq"]: BatchRecord(**{**r, "counters": np.asarray(
        r["counters"], np.int32)}) for r in d["records"]}
    run = run_of([recs[s] for s in sorted(recs)], d["events"])
    assert scan_us_per_tile.read(run) is None
