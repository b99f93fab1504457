"""The reduction on a trace recorded on one v5e: the first 40 operations,
every program and the benchmark's host spans of a 10-second window of
the search mix over an earlier configuration of 1M 128-d Gaussian rows
(``data/search_trace_d128.json``)."""
import json
import os
from types import SimpleNamespace

import pytest

from bench import trace
from bench.metrics import full_scan_fallback_share, query_scan_ms

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def events():
    with open(os.path.join(HERE, "data", "search_trace_d128.json")) as f:
        return json.load(f)


def test_window_and_programs(events):
    w0, w1 = trace.window(events)
    assert w1 - w0 == 13_783_411_363
    steps = trace.modules(events, "scan_shard", w0, w1)
    assert len(steps) == 4
    assert len(trace.modules(events, "dispatch_shard", w0, w1)) == 4
    # the run printed query_scan_ms 3440.31032225 from the whole trace
    assert query_scan_ms.read(SimpleNamespace(events=events)) == \
        pytest.approx(3440.31032225, abs=1e-9)


def test_op_names_and_busy(events):
    ops = [e for e in events if e["line"] == trace.OPS_LINE]
    assert trace.op_name(ops[0]["name"]) == "iota.1"
    w0, _ = trace.window(events)
    first = min(e["start_ns"] for e in ops)
    last = max(e["start_ns"] + e["dur_ns"] for e in ops)
    busy = trace.busy_ns(events, first, last)
    assert 0 < busy <= last - first
    gaps = trace.idle_gaps(events, w0, first)
    assert gaps[0][1] == pytest.approx((first - w0) / 1e9)


def test_fallback_seen_where_no_gather_ran(events):
    # the recorded scan steps hold no bucket_gather_pallas op in this
    # sample: every one reads as a full-scan fallback
    assert full_scan_fallback_share.read(SimpleNamespace(events=events)) \
        == 1.0
