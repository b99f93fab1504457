"""From a profiler trace to the device numbers the metrics read.

``record`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
flat list of events: each device's operations (``XLA Ops``) and programs
(``XLA Modules``), and the benchmark's own host spans (names starting
``bench.``).  Everything else reduces that list, so the reduction can be
checked on a small recorded trace without a chip.

Times are nanoseconds on the trace's clock.  The window is the host span
``bench.window`` that the harness opens around the measured window.
"""
from __future__ import annotations

import collections
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."


def op_name(text: str) -> str:
    """The HLO instruction name of a device op event, whose name is the
    instruction's text (``%bucket_search_pallas.3 = (...) custom-call(...)``)."""
    head = text.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


# control flow: the op's span holds its branch's or body's ops, which the
# trace lists as ops of their own
_CONTAINERS = ("conditional", "while", "call")


def record(log_dir: str) -> list[dict]:
    """Events of the one trace under ``log_dir``."""
    import jax
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {len(paths)}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    out = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(HOST_SPAN_PREFIX):
                    continue
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "start_ns": ev.start_ns,
                            "dur_ns": ev.duration_ns})
    return out


def union_ns(intervals) -> float:
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def window(events) -> tuple[float, float]:
    """(start, end) of the harness's ``bench.window`` span."""
    spans = [e for e in events if e["name"] == "bench.window"]
    if len(spans) != 1:
        raise ValueError(f"expected one bench.window span, got {len(spans)}")
    s = spans[0]
    return s["start_ns"], s["start_ns"] + s["dur_ns"]


def _clipped(events, line, t0, t1, plane=None):
    for e in events:
        if e["line"] != line or (plane is not None and e["plane"] != plane):
            continue
        s, t = max(e["start_ns"], t0), min(e["start_ns"] + e["dur_ns"], t1)
        if t > s:
            yield e, s, t


def devices(events) -> list[str]:
    return sorted({e["plane"] for e in events
                   if e["plane"].startswith(DEVICE_PREFIX)})


def busy_ns(events, t0, t1) -> float:
    """Busy time in [t0, t1]: the union of each device's operations,
    averaged over the devices in the trace."""
    devs = devices(events)
    if not devs:
        return 0.0
    return sum(union_ns([(s, t) for _, s, t in
                         _clipped(events, OPS_LINE, t0, t1, d)])
               for d in devs) / len(devs)


def modules(events, part: str, t0, t1) -> list[dict]:
    """Program executions in [t0, t1] whose name contains ``part``, on the
    first device (one chip's view; every chip runs every program)."""
    devs = devices(events)
    if not devs:
        return []
    return [e for e in events
            if e["plane"] == devs[0] and e["line"] == MODULES_LINE
            and part in e["name"] and t0 <= e["start_ns"] <= t1]


def ops_inside(events, module: dict) -> list[dict]:
    """Operations that ran within one program execution."""
    s, t = module["start_ns"], module["start_ns"] + module["dur_ns"]
    return [e for e in events
            if e["plane"] == module["plane"] and e["line"] == OPS_LINE
            and s <= e["start_ns"] and e["start_ns"] + e["dur_ns"] <= t]


def top_ops(events, t0, t1, n: int = 10) -> list[list]:
    """The n operations that took most device time, [instruction name,
    seconds averaged over the chips]; control-flow ops, whose time is
    that of the ops they hold, are left out."""
    tot = collections.defaultdict(float)
    devs = devices(events)
    for e, s, t in _clipped(events, OPS_LINE, t0, t1):
        name = op_name(e["name"])
        if not name.startswith(_CONTAINERS):
            tot[name] += (t - s) / 1e9 / max(len(devs), 1)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events, t0, t1, n: int = 10) -> list[list]:
    """The n longest stretches in [t0, t1] with no operation on the first
    device, each named by the innermost host span (``bench.*``) covering
    its middle: [name, seconds]."""
    devs = devices(events)
    if not devs:
        return []
    busy = sorted((s, t) for _, s, t in
                  _clipped(events, OPS_LINE, t0, t1, devs[0]))
    gaps, cur = [], t0
    for s, t in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, t)
    if t1 > cur:
        gaps.append((cur, t1))
    host = [e for e in events if e["name"].startswith(HOST_SPAN_PREFIX)
            and e["name"] != "bench.window"]
    out = []
    for s, t in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + t) / 2
        cover = [e for e in host
                 if e["start_ns"] <= mid <= e["start_ns"] + e["dur_ns"]]
        name = (min(cover, key=lambda e: e["dur_ns"])["name"] if cover
                else "host: none of the benchmark's spans")
        out.append([name, (t - s) / 1e9])
    return out
