"""``bench/spans.py``: the xplane decoding on a trace made by hand, the
scope names on paths the chip's traces hold, the report on a CPU trace
of a service that queries, writes and merges, and a capture of a tiny
run."""
import gzip
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spans, trace
from bench.tests.test_runs import CELL, TINY


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """Protobuf bytes of (number, value) fields: int as varint, str or
    bytes length-delimited."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def _entry(key, value):        # a map<int64, message> entry
    return _msg((1, key), (2, value))


def test_device_ops_decodes_names_times_and_tf_op(tmp_path):
    tf_op = _msg((1, 7), (5, "jit(scan_shard)/lsh.scan/spans/gather:"))
    plane = _msg(
        (2, "/device:TPU:0"),
        (5, _entry(7, _msg((1, 7), (2, "tf_op")))),
        (5, _entry(8, _msg((1, 8), (2, "flops")))),
        (4, _entry(1, _msg((1, 1), (2, "%fusion.1 = f32[8] fusion()"),
                           (5, tf_op), (5, _msg((1, 8), (3, 64)))))),
        (4, _entry(2, _msg((1, 2), (2, "%copy-done.3 = f32[8] copy()")))),
        (3, _msg((2, "XLA Modules"), (3, 1_000),
                 (4, _msg((1, 1), (2, 5_000), (3, 9_000))))),
        (3, _msg((2, trace.OPS_LINE), (3, 1_000),
                 (4, _msg((1, 1), (2, 2_500), (3, 3_999))),
                 (4, _msg((1, 2), (2, 10_000), (3, 1_000))))))
    host = _msg((2, "/host:CPU"), (3, _msg((2, trace.OPS_LINE))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, host), (1, plane)))
    ops = spans.device_ops(str(path))
    assert ops == [
        {"plane": "/device:TPU:0", "line": trace.OPS_LINE,
         "name": "%fusion.1 = f32[8] fusion()", "start_ns": 1_002.0,
         "dur_ns": 3.0, "tf_op": "jit(scan_shard)/lsh.scan/spans/gather:"},
        {"plane": "/device:TPU:0", "line": trace.OPS_LINE,
         "name": "%copy-done.3 = f32[8] copy()", "start_ns": 1_010.0,
         "dur_ns": 1.0, "tf_op": ""}]
    assert spans.scope_table(ops, 0, 2_000) == pytest.approx(
        {"lsh.scan/spans": 3e-6, "unscoped": 1e-6})


@pytest.mark.parametrize("tf_op, want", [
    ("jit(scan_shard)/lsh.scan/spans/gather:", "lsh.scan/spans"),
    ("jit(scan_shard)/lsh.scan/cond/branch_1_fun/fallback/"
     "jit(bucket_search_pallas)/bucket_search_pallas_fallback/pallas_call:",
     "lsh.scan/fallback"),
    ("jit(scan_shard)/lsh.scan/merge/jit(_where)/select_n:",
     "lsh.scan/merge"),
    ("jit(scan_shard)/lsh.scan/gather:", "lsh.scan"),
    ("jit(dispatch_shard)/lsh.dispatch/jit(f)/lsh.dispatch/reshape:",
     "lsh.dispatch"),
    ("jit(dispatch_shard)/lsh.dispatch/broadcast_in_dim;"
     "jit(dispatch_shard)/lsh.dispatch/reshape:", "lsh.dispatch"),
    ("jit(scan_shard)/lsh.scan/spans/add;"
     "jit(scan_shard)/lsh.scan/expand/sort:", "lsh.scan"),
    ("jit(f)/gather:", "unscoped"),
    ("", "unscoped"),
])
def test_scope(tf_op, want):
    assert spans.scope(tf_op) == want


def _index():
    from repro.compat import make_mesh
    from repro.core import DistributedLSHIndex, LSHConfig, Scheme
    cfg = LSHConfig(d=8, k=4, W=1.2, r=0.3, c=2.0, L=4, n_shards=1,
                    scheme=Scheme.LAYERED, seed=0)
    idx = DistributedLSHIndex(cfg, make_mesh((1,), ("shard",)),
                              use_kernel=True, k_neighbors=4)
    rng = np.random.default_rng(0)
    data = rng.normal(size=(640, 8)).astype(np.float32)
    idx.build(jnp.asarray(data))
    idx.compact()
    return idx, data[:24] + 0.05 * rng.normal(size=(24, 8)).astype(
        np.float32)


def test_report_reads_every_span_of_the_served_path(tmp_path):
    from repro.serving import AsyncLSHService, ShardedLSHService
    idx, queries = _index()
    sync = ShardedLSHService(idx, bucket_size=8, max_latency_ms=float("inf"))
    with AsyncLSHService(idx, bucket_size=8,
                         max_latency_ms=float("inf")) as svc:
        svc.submit_batch(queries[:8])          # compiled outside the trace
        svc.drain()
        sync.submit_batch(queries[:8])
        sync.drain()
        jax.profiler.start_trace(str(tmp_path))
        try:
            svc.submit_batch(queries[8:24])
            svc.drain()
            svc.insert(queries[:4]).result(timeout=60)
            svc.delete(np.arange(3)).result(timeout=60)
            svc.drain()
            idx.compact()       # the engine is idle: nothing is admitted
            sync.submit_batch(queries[:8])
            sync.drain()
        finally:
            jax.profiler.stop_trace()
    rep = spans.report(str(tmp_path))
    count = {k: v["count"] for k, v in rep["spans"].items()}
    assert count == {"lsh.enqueue": 2, "lsh.dispatch": 2, "lsh.scan": 2,
                     "lsh.return": 2, "lsh.retire": 2, "lsh.fetch": 2,
                     "lsh.resolve": 2, "lsh.write": 2,
                     "lsh.drain_barrier": 2, "lsh.merge": 1,
                     "lsh.query": 1}
    for v in rep["spans"].values():
        assert 0 <= v["median_ms"] <= v["max_ms"] <= v["total_ms"]
    # a CPU trace has no device plane: no scopes and no gaps to name
    assert rep["scopes_ms"] == {} and rep["idle_gaps"] == []
    write = [s for s in spans.host_spans(str(tmp_path))
             if s["name"] == "lsh.write"]
    assert sorted((s["args"]["kind"], s["args"]["rows"]) for s in write) \
        == [("delete", 3), ("insert", 4)]


def test_named_gaps_take_the_innermost_span():
    D = "/device:TPU:0"
    op = lambda s, d: {"plane": D, "line": trace.OPS_LINE, "name": "%f.1",
                       "start_ns": s, "dur_ns": d}
    host = lambda name, s, d, **a: {"plane": "/host:CPU", "line": "p",
                                    "name": name, "start_ns": s,
                                    "dur_ns": d, "args": a}
    events = [op(0, 100), op(400, 100), op(600, 400)]
    marks = [host("bench.window", 0, 1_000),
             host("lsh.retire", 50, 400, batch=3),
             host("lsh.fetch", 60, 300, batch=3),
             host("bench.submit", 510, 60)]
    got = spans.named_gaps(events, marks, 0, 1_000)
    assert [g[:2] for g in got] == [["lsh.fetch", {"batch": 3}],
                                    ["bench.submit", {}]]
    assert [g[2:] for g in got] == [pytest.approx([300e-9, 100e-9]),
                                    pytest.approx([100e-9, 500e-9])]


def test_capture_keeps_the_trace_the_batches_and_their_records(tmp_path):
    result = spans.capture(CELL, 2**31 + 11, 2.0, str(tmp_path),
                           process_start=time.perf_counter(),
                           require_tpu=False, overrides=TINY)
    assert result["correct"], result["checks"]
    with gzip.open(tmp_path / "capture.json.gz", "rt") as f:
        cap = json.load(f)
    seqs = [s for b in cap["batches"] for s in b["seqs"]]
    assert seqs == [r["seq"] for r in cap["records"]] and seqs
    assert sum(b["queries"] for b in cap["batches"]) == result["attempted"]
    enq = [e["args"]["batch"] for e in cap["events"]
           if e["name"] == "lsh.enqueue"]
    assert sorted(enq) == seqs
    assert os.path.getsize(tmp_path / "window.xplane.pb") > 0
    with open(tmp_path / "report.json") as f:
        assert json.load(f)["spans"]["lsh.enqueue"]["count"] == len(seqs)


def test_scopes_on_the_recorded_run():
    """Two scan steps on one v5e (``data/search_trace_spans.json``): the
    fallback scope is the full-scan kernel's time and little else, and
    the scopes share out every non-container op's time."""
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "data", "search_trace_spans.json")) as f:
        events = json.load(f)["events"]
    w0, w1 = trace.window(events)
    table = spans.scope_table(events, w0, w1)
    assert {"lsh.dispatch", "lsh.return", "lsh.scan", "lsh.scan/spans",
            "lsh.scan/expand", "lsh.scan/merge",
            "lsh.scan/fallback"} <= set(table)
    ops = [e for e in events if e["line"] == trace.OPS_LINE
           and not e["name"].startswith(trace._CONTAINERS)]
    kernel = sum(e["dur_ns"] for e in ops
                 if e["name"].startswith("bucket_search_pallas_fallback"))
    assert kernel / 1e6 <= table["lsh.scan/fallback"] <= kernel / 1e6 + 1.0
    assert sum(table.values()) == pytest.approx(
        sum(e["dur_ns"] for e in ops) / 1e6)
