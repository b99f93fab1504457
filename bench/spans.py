"""The served path's own spans and device scopes in a profiler trace, and
a traced run of a cell that keeps them.

``bench/trace.record`` keeps the benchmark's ``bench.*`` spans and each
device operation by its instruction name.  This module reads what it
leaves out:

* the program's host spans (``lsh.*``, ``repro.core.spans``) with their
  args (``batch``, ``reason``, ``take``, ``kind``, ``rows``, ``n_live``);
* the named scope each device operation ran under (``jax.named_scope``
  on the three query stages and inside the scan, e.g.
  ``lsh.scan/fallback``).  The trace holds it in the op's ``tf_op``
  metadata, which ``jax.profiler.ProfileData`` does not expose, so
  ``device_ops`` decodes the ``.xplane.pb`` for it.

Report, on any profiler trace of a serving process (the directory that
``jax.profiler.trace`` wrote, or its ``.xplane.pb``):

  python -m bench.spans report TRACE [--top 10]

prints one JSON object: per span name its count and times in ms (total,
median, max); per device scope its time in ms; and the first device's
longest idle gaps, each named by the innermost ``bench.*`` or ``lsh.*``
span over its middle.  What it answers:

* what a write costs the queries: ``lsh.drain_barrier`` is the time a
  write waited for the query batches in flight, ``lsh.write`` the whole
  write, and the gaps under them are the device's idle share;
* what a merge costs them: ``lsh.merge`` (nothing is admitted meanwhile);
* the host's part of a batch: ``lsh.enqueue`` (staging and the three
  stage calls, each ``lsh.dispatch`` / ``lsh.scan`` / ``lsh.return``, or
  ``lsh.query`` for the fused step), ``lsh.fetch`` (the host waiting on
  the device) and ``lsh.resolve`` (the handle loop);
* the scan step's device time by part: span lookup (``spans``), probe
  expansion and sort (``expand``), the CSR gather (``gather``), the tail
  scan (``tail``), the overflow fallback (``fallback``) and the merges.

Capture: one traced run of a cell exactly as ``bench/run.py`` makes it,
keeping what the harness discards (the trace file, the window's batches
and their ``BatchRecord`` s):

  python -m bench.spans capture --workload random1m.search --seed N \\
      --out DIR [--seconds 20]

writes ``DIR/window.xplane.pb``, ``DIR/capture.json.gz`` and
``DIR/report.json``, and prints the run's result line last.  It wraps
two harness functions (``trace.record``, ``harness._compare``) for the
run's length to keep their inputs.  The recorded fixture of the readers'
tests is cut from a capture by

  python -m bench.spans fixture DIR OUT.json
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import glob
import gzip
import json
import os
import shutil
import statistics
import sys
import time

from bench import trace

HOST_PREFIXES = ("bench.", "lsh.")
# the scan's own scopes (kernels/ops.py, core/index.py); the stage
# scopes are the ``lsh.*`` ones
SCAN_SCOPES = ("spans", "expand", "gather", "tail", "fallback", "merge")


def _xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {path}, "
                                f"found {len(found)}")
    return found[0]


# ---------------------------------------------------------------------------
# Reading
# ---------------------------------------------------------------------------

def host_spans(path: str) -> list[dict]:
    """The ``bench.*`` and ``lsh.*`` spans on the host planes, in the
    events' form of ``bench/trace.record`` plus ``args``."""
    import jax
    out = []
    for plane in jax.profiler.ProfileData.from_file(_xplane(path)).planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_PREFIXES):
                    out.append({"plane": plane.name, "line": line.name,
                                "name": ev.name, "start_ns": ev.start_ns,
                                "dur_ns": ev.duration_ns,
                                "args": {k: v for k, v in ev.stats}})
    return out


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf: bytes) -> dict:
    """A protobuf message's fields: {number: [values]} (varints as int,
    length-delimited as bytes)."""
    out = collections.defaultdict(list)
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {kind}")
        out[key >> 3].append(v)
    return out


def _text(f: dict, n: int) -> str:
    return f[n][0].decode(errors="replace") if f.get(n) else ""


def device_ops(path: str) -> list[dict]:
    """Every device operation (``XLA Ops``), in the events' form of
    ``bench/trace.record`` plus ``tf_op``: the path of jit, scopes and
    primitive it was traced under.

    From the xplane proto (tsl/profiler/protobuf/xplane.proto): XSpace
    1 planes; XPlane 2 name, 3 lines, 4 event_metadata, 5 stat_metadata;
    XLine 2 name, 3 timestamp_ns, 4 events; XEvent 1 metadata_id,
    2 offset_ps, 3 duration_ps; XEventMetadata 1 id, 2 name, 5 stats;
    XStatMetadata 1 id, 2 name; XStat 1 metadata_id, 5 str_value.
    """
    with open(_xplane(path), "rb") as f:
        space = _fields(f.read())
    out = []
    for raw_plane in space.get(1, []):
        plane = _fields(raw_plane)
        pname = _text(plane, 2)
        if not pname.startswith(trace.DEVICE_PREFIX):
            continue
        stat_names = {}
        for entry in plane.get(5, []):
            meta = _fields(_fields(entry)[2][0])
            stat_names[meta[1][0] if meta.get(1) else 0] = _text(meta, 2)
        names, tf_op = {}, {}
        for entry in plane.get(4, []):
            meta = _fields(_fields(entry)[2][0])
            mid = meta[1][0] if meta.get(1) else 0
            names[mid] = _text(meta, 2)
            for raw_stat in meta.get(5, []):
                stat = _fields(raw_stat)
                if (stat_names.get(stat[1][0] if stat.get(1) else 0)
                        == "tf_op" and stat.get(5)):
                    tf_op[mid] = _text(stat, 5)
        for raw_line in plane.get(3, []):
            line = _fields(raw_line)
            if _text(line, 2) != trace.OPS_LINE:
                continue
            t0 = line[3][0] if line.get(3) else 0
            for raw_ev in line.get(4, []):
                ev = _fields(raw_ev)
                mid = ev[1][0] if ev.get(1) else 0
                out.append({"plane": pname, "line": trace.OPS_LINE,
                            "name": names.get(mid, ""),
                            "start_ns": float(t0 + (ev[2][0] if ev.get(2)
                                                    else 0) // 1000),
                            "dur_ns": float((ev[3][0] if ev.get(3) else 0)
                                            // 1000),
                            "tf_op": tf_op.get(mid, "")})
    return out


def scope(tf_op: str) -> str:
    """The program's named scopes in an op's ``tf_op``: its ``lsh.*``
    stage and the scan's parts under it, e.g. ``lsh.scan/fallback`` for
    ``jit(scan_shard)/lsh.scan/cond/branch_1_fun/fallback/.../pallas_call:``.
    A fused op lists one path per part (``;``) and takes the scopes they
    share; an op outside every stage, or one the compiler added with no
    path, is ``unscoped``."""
    paths = []
    for path in tf_op.rstrip(":").split(";"):
        kept = []
        for p in path.split("/")[:-1]:          # the primitive left out
            # a stage body traced inside its own jit repeats its scope
            if (p.startswith("lsh.") or p in SCAN_SCOPES) \
                    and p not in kept[-1:]:
                kept.append(p)
        paths.append(kept if kept and kept[0].startswith("lsh.") else [])
    shared = []
    for parts in zip(*paths):
        if len(set(parts)) > 1:
            break
        shared.append(parts[0])
    return "/".join(shared) or "unscoped"


# ---------------------------------------------------------------------------
# Reductions
# ---------------------------------------------------------------------------

def span_table(spans: list[dict]) -> dict:
    """{name: {count, total_ms, median_ms, max_ms}} over the spans."""
    by = collections.defaultdict(list)
    for s in spans:
        by[s["name"]].append(s["dur_ns"] / 1e6)
    return {k: {"count": len(v), "total_ms": sum(v),
                "median_ms": statistics.median(v), "max_ms": max(v)}
            for k, v in sorted(by.items())}


def scope_table(ops: list[dict], t0: float, t1: float) -> dict:
    """{scope: ms}: the first device's op time in [t0, t1] by the scope
    it ran under (``scope``), control-flow containers left out as in
    ``trace.top_ops``."""
    devs = trace.devices(ops)
    tot = collections.defaultdict(float)
    for e, s, t in trace._clipped(ops, trace.OPS_LINE, t0, t1,
                                  devs[0] if devs else None):
        if not trace.op_name(e["name"]).startswith(trace._CONTAINERS):
            tot[scope(e["tf_op"])] += (t - s) / 1e6
    return dict(sorted(tot.items(), key=lambda kv: -kv[1]))


def named_gaps(events: list[dict], spans: list[dict], t0: float, t1: float,
               n: int = 10) -> list[list]:
    """The n longest stretches of [t0, t1] with no operation on the first
    device, as ``trace.idle_gaps`` finds them, each named by the innermost
    span of ``spans`` over its middle: [name, args, seconds, seconds
    from t0]."""
    devs = trace.devices(events)
    if not devs:
        return []
    busy = sorted((s, t) for _, s, t in
                  trace._clipped(events, trace.OPS_LINE, t0, t1, devs[0]))
    gaps, cur = [], t0
    for s, t in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, t)
    if t1 > cur:
        gaps.append((cur, t1))
    host = [e for e in spans if e["name"] != "bench.window"]
    out = []
    for s, t in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + t) / 2
        cover = [e for e in host
                 if e["start_ns"] <= mid <= e["start_ns"] + e["dur_ns"]]
        inner = min(cover, key=lambda e: e["dur_ns"]) if cover else None
        out.append([inner["name"] if inner else "host: no span",
                    inner.get("args", {}) if inner else {},
                    (t - s) / 1e9, (s - t0) / 1e9])
    return out


def report(path: str, top: int = 10) -> dict:
    """Spans, device scopes and named idle gaps of one trace; over the
    ``bench.window`` span where the trace has one, else over all of it."""
    spans, ops = host_spans(path), device_ops(path)
    windows = [e for e in spans if e["name"] == "bench.window"]
    if len(windows) == 1:
        t0, t1 = trace.window(windows)
        spans = [e for e in spans if t0 <= e["start_ns"] <= t1]
    else:
        ends = [(e["start_ns"], e["start_ns"] + e["dur_ns"])
                for e in spans + ops]
        t0 = min((s for s, _ in ends), default=0.0)
        t1 = max((t for _, t in ends), default=0.0)
    return {"window_s": (t1 - t0) / 1e9, "spans": span_table(spans),
            "scopes_ms": scope_table(ops, t0, t1),
            "idle_gaps": named_gaps(ops, spans, t0, t1, top)}


# ---------------------------------------------------------------------------
# Capture and the recorded fixture
# ---------------------------------------------------------------------------

def capture(workload: str, seed: int, seconds: float, out: str, *,
            process_start: float, require_tpu: bool = True,
            overrides: dict | None = None) -> dict:
    """One traced run of the cell (``harness.run_cell``) that keeps the
    trace file, the window's batches and their records under ``out``."""
    from bench import harness
    os.makedirs(out, exist_ok=True)
    kept = {}
    record, compare = trace.record, harness._compare

    def keep_trace(log_dir):
        shutil.copy(_xplane(log_dir), os.path.join(out, "window.xplane.pb"))
        return record(log_dir)

    def keep_run(run, live, limits):
        kept["run"] = run
        return compare(run, live, limits)

    trace.record, harness._compare = keep_trace, keep_run
    try:
        result = harness.run_cell(workload, seed, seconds, True,
                                  process_start=process_start,
                                  require_tpu=require_tpu,
                                  overrides=overrides)
    finally:
        trace.record, harness._compare = record, compare
    run = kept["run"]
    path = os.path.join(out, "window.xplane.pb")
    lsh = [e for e in host_spans(path) if e["name"].startswith("lsh.")]
    tf_op = {(e["plane"], e["name"], e["start_ns"]): e["tf_op"]
             for e in device_ops(path)}
    events = [dict(e, tf_op=tf_op[(e["plane"], e["name"], e["start_ns"])])
              if e["line"] == trace.OPS_LINE else e for e in run.events]
    recs = {}
    batches = []
    for b in run.batches:
        seqs = sorted({h.batch.seq for h in b.handles
                       if getattr(h, "batch", None) is not None})
        for h in b.handles:
            if getattr(h, "batch", None) is not None:
                recs[h.batch.seq] = h.batch
        batches.append({"t_submit": b.t_submit, "t_done": b.t_done,
                        "queries": len(b.handles), "seqs": seqs})
    data = {"workload": workload, "seed": seed, "result": result,
            "t0": run.t0, "events": events + lsh, "batches": batches,
            "records": [_record_json(recs[k]) for k in sorted(recs)]}
    with gzip.open(os.path.join(out, "capture.json.gz"), "wt") as f:
        json.dump(data, f)
    with open(os.path.join(out, "report.json"), "w") as f:
        json.dump(report(path), f, indent=1)
    return result


def _record_json(rec) -> dict:
    return {k: (v.tolist() if hasattr(v, "tolist") else v)
            for k, v in dataclasses.asdict(rec).items()}


def fixture(capture_dir: str) -> dict:
    """The stretch of a capture from the window's start to the first
    steady-state batch's enqueue (the first batch with no query submitted
    before the window's first answer): to the later of the end of its
    ``lsh.enqueue`` span and of its ``dispatch_shard`` program, with the
    batches and records enqueued in it.  The ``bench.window`` span is cut
    to that stretch; it keeps the device events that ended in it and the
    host spans that began in it; device ops keep only their instruction
    name (what ``trace.op_name`` reads) beside their ``tf_op``."""
    with gzip.open(os.path.join(capture_dir, "capture.json.gz"), "rt") as f:
        cap = json.load(f)
    events = cap["events"]
    w0, _ = trace.window(events)
    first = min(b["t_done"] for b in cap["batches"])
    early = {s for b in cap["batches"] if b["t_submit"] < first
             for s in b["seqs"]}
    steady = min(r["seq"] for r in cap["records"]
                 if r["seq"] not in early)
    recs = [r for r in cap["records"] if r["seq"] <= steady]
    programs = sorted((e for e in trace.modules(events, "dispatch_shard",
                                                w0, float("inf"))),
                      key=lambda e: e["start_ns"])
    program = programs[len(recs) - 1]
    enqueue, = (e for e in events if e["name"] == "lsh.enqueue"
                and e["args"]["batch"] == steady)
    cut = max(program["start_ns"] + program["dur_ns"],
              enqueue["start_ns"] + enqueue["dur_ns"])
    kept = []
    for e in events:
        if e["name"] == "bench.window":
            kept.append(dict(e, dur_ns=cut - w0))
        elif e["plane"].startswith(trace.DEVICE_PREFIX):
            if e["start_ns"] + e["dur_ns"] <= cut:
                kept.append(dict(e, name=trace.op_name(e["name"]))
                            if e["line"] == trace.OPS_LINE else e)
        elif e["start_ns"] < cut:
            kept.append(e)
    seqs = {r["seq"] for r in recs}
    return {"made_by": f"python -m bench.spans fixture <capture of "
                       f"{cap['workload']} seed {cap['seed']}>",
            "t0": cap["t0"], "events": kept, "records": recs,
            "batches": [b for b in cap["batches"]
                        if set(b["seqs"]) & seqs]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    rp = sub.add_parser("report")
    rp.add_argument("trace")
    rp.add_argument("--top", type=int, default=10)
    cp = sub.add_parser("capture")
    cp.add_argument("--workload", required=True)
    cp.add_argument("--seed", type=int, required=True)
    cp.add_argument("--seconds", type=float, default=20.0)
    cp.add_argument("--out", required=True)
    fp = sub.add_parser("fixture")
    fp.add_argument("capture_dir")
    fp.add_argument("out")
    args = ap.parse_args(argv)
    if args.cmd == "report":
        print(json.dumps(report(args.trace, args.top)))
    elif args.cmd == "fixture":
        with open(args.out, "w") as f:
            json.dump(fixture(args.capture_dir), f)
    else:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        sys.path[:0] = [os.path.join(root, "src"), root]
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              os.path.join(root, ".jax_cache"))
        from repro.compile_cache import enable_compile_cache
        enable_compile_cache()
        result = capture(args.workload, args.seed, args.seconds, args.out,
                         process_start=_START)
        print(json.dumps(result), flush=True)
    return 0


_START = time.perf_counter()

if __name__ == "__main__":
    sys.exit(main())
