"""The readers of what the served path records about itself: each batch's
``BatchRecord`` (on every answered handle) and the device trace around it.

On small runs built by hand; on a run recorded on one v5e with this
program's spans and records; and against a program that records nothing
(the readers then read nothing and raise nothing).  The readers that
were there before read what they read before on the earlier recorded
trace (``data/search_trace_d128.json``).

``data/search_trace_spans.json`` is the first 8.71 s of a traced
``random1m.search`` window on one TPU v5e, made by

  python -m bench.spans capture --workload random1m.search \
      --seed 2718281857 --seconds 20 --out DIR
  python -m bench.spans fixture DIR bench/tests/data/search_trace_spans.json

``fixture`` keeps the stretch from the window's start to the first
steady-state batch's enqueue (batch 4; batches 2 and 3 filled the closed
loop), the records and client batches of batches 2-4, the device events
that ended in it with each op's instruction name and ``tf_op``, and the
host spans (``bench.*``, ``lsh.*`` with their args) that began in it;
the ``bench.window`` span is cut to the stretch.
"""
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from bench import trace
from bench.metrics import (_batches, admission_wait_ms, device_idle_share,
                           device_queue_ms, full_scan_fallback_share,
                           gather_window_need, probe_live_share,
                           query_scan_ms, scan_fallback_share, scan_glue_ms,
                           scan_roofline)
from bench.tests.test_trace import D0, EVENTS, ev
from repro.serving import BatchRecord

HERE = os.path.dirname(os.path.abspath(__file__))
NEW = (admission_wait_ms, device_queue_ms, scan_glue_ms, scan_fallback_share,
       gather_window_need, probe_live_share)


def rec(seq, *, fallback=1, need=10, G=8, live=30, expanded=1024,
        take=64, wait_s=0.0, t_enqueue=0.0, t_enqueued=0.0):
    return BatchRecord(
        seq=seq, reason="full", take=take, pad=0, drops=0, routed_rows=take,
        admission_wait_s=wait_s, t_enqueue=t_enqueue, t_enqueued=t_enqueued,
        counters=np.array([[fallback, need, G, live, expanded]], np.int32))


def run_of(records, events=None, t0=0.0, per_batch=2, times=None):
    """A run whose window batches hold these records' handles; batch i
    is submitted at ``times[i][0]`` and answered at ``times[i][1]``
    (by default at i and i + 0.5 s: the first batch alone comes before
    the window's first answer)."""
    times = times or [(i, i + 0.5) for i in range(len(records))]
    batches = [SimpleNamespace(handles=[SimpleNamespace(batch=r)] * per_batch,
                               t_submit=ts, t_done=td)
               for r, (ts, td) in zip(records, times)]
    return SimpleNamespace(batches=batches, events=events, t0=t0)


def test_counter_readers():
    recs = [rec(0, fallback=1, need=12, G=8, live=30, take=64, wait_s=6.4),
            rec(1, fallback=0, need=6, G=8, live=50, take=32, wait_s=0.0),
            rec(2, fallback=1, need=9, G=8, live=20, take=64, wait_s=3.2)]
    run = run_of(recs)
    assert scan_fallback_share.read(run) == pytest.approx(2 / 3)
    assert gather_window_need.read(run) == pytest.approx(9 / 8)   # median
    assert probe_live_share.read(run) == pytest.approx(100 / 3072)
    # the steady state only: batch 0 came before the first answer
    assert admission_wait_ms.read(run) == pytest.approx(1e3 * 3.2 / 96)


def test_steady_state_leaves_out_the_closed_loop_fill():
    # two buckets submitted at the window's start, answered at 4 and 8;
    # the next two submitted as those answered
    recs = [rec(i, take=64, wait_s=w)
            for i, w in enumerate([0.001, 0.002, 256.0, 262.4])]
    run = run_of(recs, times=[(0, 4), (0, 8), (4, 12), (8, 16)])
    assert [r.seq for r in _batches.steady(run)] == [2, 3]
    assert admission_wait_ms.read(run) == pytest.approx(1e3 * 518.4 / 128)
    # a window that ends before any batch of the steady state reads nothing
    assert admission_wait_ms.read(run_of(recs[:2], times=[(0, 4), (0, 8)])) \
        is None


def test_steps_off_the_csr_path():
    run = run_of([rec(0, fallback=0, need=0, G=0, live=0, expanded=0)])
    assert scan_fallback_share.read(run) == 0.0
    assert gather_window_need.read(run) is None
    assert probe_live_share.read(run) is None


def test_a_program_that_records_nothing_reads_nothing():
    """Handles without ``batch`` (a program from before the record), and
    a trace without the programs the readers look for."""
    class Handle:
        __slots__ = ("gids",)
    bare = SimpleNamespace(batches=[SimpleNamespace(
        handles=[Handle()], t_submit=0.0, t_done=1.0)], events=None, t0=0.0)
    for reader in NEW:
        assert reader.read(bare) is None, reader.__name__
    traced = SimpleNamespace(batches=bare.batches, events=EVENTS, t0=0.0)
    assert device_queue_ms.read(traced) is None


def test_scan_glue_leaves_out_kernels_and_containers():
    # step 1: fusion.2 (250 ns) beside the gather kernel and the
    # conditional that holds it; step 2: only the full-scan kernel
    run = SimpleNamespace(events=EVENTS)
    assert scan_glue_ms.read(run) == pytest.approx(250 / 2 / 1e6)


def test_device_queue_pairs_first_in_first_out():
    # window [100, 10_100) ns; t0 = 5.0 s on the service's clock is 100 ns
    events = [ev("/host:CPU", "python", "bench.window", 100, 10_000),
              ev(D0, trace.MODULES_LINE, "jit_dispatch_shard(1)", 1_100, 50),
              ev(D0, trace.MODULES_LINE, "jit_dispatch_shard(1)", 2_000, 50),
              ev(D0, trace.MODULES_LINE, "jit_dispatch_shard(1)", 8_000, 50)]
    s = lambda ns: 5.0 + (ns - 100) / 1e9     # trace ns -> service clock
    recs = [rec(0, t_enqueue=s(200), t_enqueued=s(600)),
            rec(1, t_enqueue=s(1_500), t_enqueued=s(2_500)),
            rec(2, t_enqueue=s(3_000), t_enqueued=s(3_200))]
    run = run_of(recs, events, t0=5.0)
    # each takes the first unpaired program after it left admission;
    # batch 1's began before its enqueue ended, so it waited 0; batch 0,
    # before the window's first answer, is paired but not counted
    want = [1_100 - 600, 0, 8_000 - 3_200]
    assert device_queue_ms.read(run) == pytest.approx(
        sum(want[1:]) / 2 / 1e6, rel=1e-6)
    # a record whose enqueue is not on the window's clock reads nothing
    off = [rec(0, t_enqueue=s(200), t_enqueued=s(600)),
           rec(1, t_enqueue=900.0, t_enqueued=900.1)]
    assert device_queue_ms.read(run_of(off, events, t0=5.0)) is None
    # a batch with no program left to pair reads nothing
    run = run_of(recs + [rec(3, t_enqueue=s(9_000), t_enqueued=s(9_100))],
                 events, t0=5.0)
    assert device_queue_ms.read(run) is None


def test_earlier_readers_read_as_before_on_the_earlier_trace():
    with open(os.path.join(HERE, "data", "search_trace_d128.json")) as f:
        events = json.load(f)
    run = SimpleNamespace(events=events, device_kind="TPU v5 lite",
                          scan_work=lambda: [(2.0e9, 5.0e8)] * 4)
    assert query_scan_ms.read(run) == 3440.31032225
    assert full_scan_fallback_share.read(run) == 1.0
    assert device_idle_share.read(run) == 99.99979419463547
    assert scan_roofline.read(run) == 0.0177455099486879


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "search_trace_spans.json")) as f:
        d = json.load(f)
    recs = {r["seq"]: BatchRecord(**{**r, "counters": np.asarray(
        r["counters"], np.int32)}) for r in d["records"]}
    batches = []
    for b in d["batches"]:
        seq, = b["seqs"]
        batches.append(SimpleNamespace(
            handles=[SimpleNamespace(batch=recs[seq])] * b["queries"],
            t_submit=b["t_submit"], t_done=b["t_done"]))
    return d, SimpleNamespace(batches=batches, events=d["events"],
                              t0=d["t0"])


def test_readers_on_the_recorded_run(recorded):
    _, run = recorded
    # every batch fell back: the most demanding row tile needed 3,018,
    # 3,053 and 3,017 aligned tiles against a window of 776
    assert scan_fallback_share.read(run) == 1.0
    assert full_scan_fallback_share.read(run) == 1.0
    assert gather_window_need.read(run) == pytest.approx(3018 / 776)
    assert probe_live_share.read(run) == pytest.approx(1063 / 142080)
    # batches 2 and 3 were submitted at the window's start, before its
    # first answer; batch 4, the steady state, sat in admission while the
    # engine retired batch 3, then found the device idle (its dispatch
    # program began 2.17 ms before its enqueue span ended)
    assert [r.seq for r in _batches.steady(run)] == [4]
    assert admission_wait_ms.read(run) == pytest.approx(
        1e3 * 278.1807937920021 / 64)
    assert device_queue_ms.read(run) == 0.0
    assert scan_glue_ms.read(run) == pytest.approx(50.6330265)
    assert query_scan_ms.read(run) == pytest.approx(4347.6707075)


def test_device_queue_counts_each_batch_it_is_asked_to(recorded):
    """Taken as steady (each batch submitted after the one before it was
    answered), batch 3 reads the 4,343.9 ms it waited behind batch 2's
    scan step on the device."""
    d, run = recorded
    times = [(float(i), i + 0.5) for i in range(len(run.batches))]
    moved = SimpleNamespace(
        batches=[SimpleNamespace(handles=b.handles, t_submit=ts, t_done=td)
                 for b, (ts, td) in zip(run.batches, times)],
        events=run.events, t0=run.t0)
    assert [r.seq for r in _batches.steady(moved)] == [3, 4]
    assert device_queue_ms.read(moved) == pytest.approx(
        4343.905759999991 / 2)


def test_recorded_spans_agree_with_the_records(recorded):
    d, run = recorded
    w0, _ = trace.window(d["events"])
    spans = [e for e in d["events"] if e["name"].startswith("lsh.")]
    enq = {e["args"]["batch"]: e for e in spans if e["name"] == "lsh.enqueue"}
    for r in (h.batch for b in run.batches for h in b.handles[:1]):
        on_trace = lambda t: w0 + (t - d["t0"]) * 1e9
        # the service's clock, put on the trace's through the window's
        # start, lands within 50 us of the span's own ends
        e = enq[r.seq]
        assert abs(on_trace(r.t_enqueue) - e["start_ns"]) < 50_000
        assert abs(on_trace(r.t_enqueued)
                   - (e["start_ns"] + e["dur_ns"])) < 50_000
        assert e["args"] == {"batch": r.seq, "reason": "full", "take": 64}
    for name in ("lsh.dispatch", "lsh.scan", "lsh.return"):
        assert sorted(e["args"]["batch"] for e in spans
                      if e["name"] == name) == sorted(enq), name
    retire = [e for e in spans if e["name"] == "lsh.retire"]
    fetch = [e for e in spans if e["name"] == "lsh.fetch"]
    assert [e["args"] for e in retire] == [e["args"] for e in fetch] == [
        {"batch": 2}, {"batch": 3}]
    assert all(r["start_ns"] <= f["start_ns"] for r, f in zip(retire, fetch))
