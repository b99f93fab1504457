"""The trace reduction, on a small trace in the recorded event format."""
import pytest

from bench import trace

D0, D1 = "/device:TPU:0", "/device:TPU:1"


def ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur}


EVENTS = [
    ev("/host:CPU", "python", "bench.window", 100, 1000),
    ev("/host:CPU", "python", "bench.wait_answer", 500, 400),
    ev("/host:CPU", "python", "bench.submit", 950, 100),
    # device 0: a scan step [100, 500) holding a gather op, ops overlap
    ev(D0, trace.MODULES_LINE, "jit_scan_shard(1)", 100, 400),
    ev(D0, trace.OPS_LINE, "%conditional = (f32[2307,10]) conditional(s32[] "
       "%c), branch_computations={%region_13.61.clone, %region_19.63}",
       100, 400),
    ev(D0, trace.OPS_LINE, "%bucket_gather_pallas.1 = (f32[2432,128]) "
       "custom-call(f32[2432,128] %custom-call.178), "
       'custom_call_target="tpu_custom_call"', 100, 200),
    ev(D0, trace.OPS_LINE, "%fusion.2 = f32[4] fusion(), kind=kLoop", 250, 250),
    # a second scan step [600, 800) with only a full scan
    ev(D0, trace.MODULES_LINE, "jit_scan_shard(1)", 600, 200),
    ev(D0, trace.OPS_LINE, "%bucket_search_pallas.3 = (f32[2432,128]) "
       "custom-call(f32[4000000,128] %get-tuple-element.58)", 600, 200),
    # an op before the window is clipped away
    ev(D0, trace.OPS_LINE, "%fusion.0 = f32[4] fusion()", 0, 150),
    # device 1 busy [100, 1100): the whole window
    ev(D1, trace.OPS_LINE, "%fusion.9 = f32[4] fusion()", 100, 1000),
]


def test_window_and_union():
    assert trace.window(EVENTS) == (100, 1100)
    assert trace.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert trace.union_ns([]) == 0


def test_busy_is_the_union_averaged_over_devices():
    # device 0: [100, 500) + [600, 800) = 600; device 1: 1000
    assert trace.busy_ns(EVENTS, 100, 1100) == pytest.approx(800)


def test_modules_and_ops_inside():
    steps = trace.modules(EVENTS, "scan_shard", 100, 1100)
    assert [s["start_ns"] for s in steps] == [100, 600]
    assert [trace.op_name(o["name"])
            for o in trace.ops_inside(EVENTS, steps[0])] == [
        "conditional", "bucket_gather_pallas.1", "fusion.2"]
    assert [trace.op_name(o["name"])
            for o in trace.ops_inside(EVENTS, steps[1])] == [
        "bucket_search_pallas.3"]


def test_top_ops_and_idle_gaps():
    top = dict(trace.top_ops(EVENTS, 100, 1100))
    assert "conditional" not in top          # it holds the ops below it
    assert top["bucket_gather_pallas.1"] == pytest.approx(200 / 2 / 1e9)
    assert top["fusion.9"] == pytest.approx(1000 / 2 / 1e9)
    assert "fusion.0" in top and top["fusion.0"] == pytest.approx(50 / 2e9)
    gaps = trace.idle_gaps(EVENTS, 100, 1100)
    # device 0 idle [500, 600) under the wait, [800, 1100) under submit
    assert gaps[0] == ["bench.submit", pytest.approx(300 / 1e9)]
    assert gaps[1] == ["bench.wait_answer", pytest.approx(100 / 1e9)]


def test_readers_on_the_recorded_trace():
    from types import SimpleNamespace
    from bench.metrics import (device_idle_share, full_scan_fallback_share,
                               query_scan_ms)
    run = SimpleNamespace(events=EVENTS)
    assert query_scan_ms.read(run) == pytest.approx(300 / 1e6)
    assert full_scan_fallback_share.read(run) == 0.5
    assert device_idle_share.read(run) == pytest.approx(20.0)
    assert query_scan_ms.read(SimpleNamespace(events=None)) is None
