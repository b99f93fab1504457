"""One run of one benchmark cell, driven by ``BENCHMARK.json`` and data.

The cell names a configuration (``bench/configs/<config>.json``: the
deployment's dataset, index parameters and guarantees) and a traffic mix
(``bench/traffic/<mix>.json``, read by ``bench/traffic.py``).  Metrics are
read by one module each, ``bench/metrics/<metric>.py``, found by the name
in ``BENCHMARK.json``.  A cell, configuration, mix or metric is added as
files and entries; nothing here names one.

A run:
  1. looks for the chips the cell asks for and refuses to run without;
  2. makes the dataset on the device from the configuration's seed;
  3. builds the index on a mesh of the cell's chips with the Pallas
     kernels, compacts it, applies the mix's set-up writes, and warms the
     window's shapes with queries of its own (set-up ends here);
  4. drives ``AsyncLSHService`` for ``--seconds`` with the mix, from one
     thread, timing each request on the host clock (``--trace 1`` records
     a profiler trace of the window as well);
  5. waits for every answer admitted in the window, reads the device's
     peak memory, frees the index, and compares every answer with the
     plain reference over the live set (``bench/check.py``);
  6. prints the comparison on standard error and one JSON line last.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
RESULT_TIMEOUT_S = 120.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips the cell needs."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def merged(base: dict, over: Optional[dict]) -> dict:
    """``base`` with the keys of ``over`` replaced (nested dicts merged)."""
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = merged(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


@dataclasses.dataclass
class Batch:
    """One submitted bucket of queries and what came back."""
    q: np.ndarray              # (b, d) queries; slot i has qid i
    handles: list
    t_submit: float
    t_done: float = 0.0
    failed: int = 0


@dataclasses.dataclass
class Run:
    """What a run measured: the input of every metric reader."""
    setup_s: float = 0.0
    t0: float = 0.0            # window start (host perf_counter)
    t1: float = 0.0            # last answer of the window
    batches: list = dataclasses.field(default_factory=list)
    device_kind: str = ""
    events: Optional[list] = None       # trace events (--trace 1)
    reference: object = None
    set_up_live: Optional[np.ndarray] = None
    _work: Optional[list] = None

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    @property
    def queries(self) -> int:
        return sum(len(b.handles) for b in self.batches)

    @property
    def answered(self) -> int:
        return sum(len(b.handles) - b.failed for b in self.batches)

    def latencies_ms(self) -> np.ndarray:
        """Submit-to-answer time of every query of the window; a query
        that failed or never came back counts as infinitely late."""
        out = []
        for b in self.batches:
            ms = (b.t_done - b.t_submit) * 1e3
            out += [ms] * (len(b.handles) - b.failed) + [np.inf] * b.failed
        return np.asarray(out, np.float64)

    def scan_work(self) -> list:
        """(flops, bytes) each window batch's scan needs (bench/roofline)."""
        if self._work is None:
            from bench import roofline
            from bench.reference.lsh import probe_buckets
            ref = self.reference
            T, k = ref.spec.n_tables, ref.spec.k
            hk = np.moveaxis(np.asarray(ref.hks), 1, 0).reshape(T, -1, k)
            live = hk[:, :len(self.set_up_live)][:, self.set_up_live]
            probes = [np.asarray(probe_buckets(
                ref.spec, ref.A, ref.b, ref.okeys,
                np.arange(len(b.q), dtype=np.int32), b.q,
                ref.hash_precision))
                for b in self.batches]
            self._work = roofline.scan_work(live, probes, ref.spec.d)
        return self._work


# ---------------------------------------------------------------------------
# The window's loop
# ---------------------------------------------------------------------------

def _wait(batch: Batch) -> None:
    for h in batch.handles:
        try:
            h.result(timeout=RESULT_TIMEOUT_S)
        except Exception as exc:   # noqa: BLE001 -- a failed answer is a result
            batch.failed += 1
            log(f"query failed: {exc!r}")
    batch.t_done = time.perf_counter()


def _closed_loop(svc, traffic, rows, seconds, run, annotate):
    inflight = collections.deque()
    t_stop = run.t0 + seconds

    def submit():
        q, _ = traffic.plant(rows)
        b = Batch(q=q, handles=[], t_submit=time.perf_counter())
        with annotate("bench.submit"):
            b.handles = svc.submit_batch(q)
        inflight.append(b)
        run.batches.append(b)

    for _ in range(traffic.outstanding):
        submit()
    while inflight:
        b = inflight.popleft()
        with annotate("bench.wait_answer"):
            _wait(b)
        if time.perf_counter() < t_stop:
            submit()


def _set_up_writes(svc, traffic, rows, n, extra) -> np.ndarray:
    """The mix's writes before the window (see bench/traffic.py); returns
    the live set over the ``n + extra`` rows."""
    live = np.ones((n + extra,), bool)
    if extra:
        svc.insert(rows[n:], gids=np.arange(n, n + extra)).result()
        dels = traffic.setup_deletes(n, np.arange(n, n + extra))
        svc.delete(dels).result()
        live[dels] = False
    return live


def _compare(run: Run, live: np.ndarray, limits: dict) -> dict:
    """Every answer of the window against the reference over the live
    set (bench/check.py)."""
    from bench import check
    ref = run.reference
    # slot i of a bucket is the query's id (its entropy offsets' seed)
    got = [(b.q[i], i, h) for b in run.batches
           for i, h in enumerate(b.handles)]
    unanswered = sum(h.gids is None for _, _, h in got)
    got = [x for x in got if x[2].gids is not None]
    K = ref.spec.K
    if not got:
        empty = np.zeros((0, K), np.float32)
        return check.compare(empty, empty.astype(np.int32), empty, empty,
                             empty.astype(np.int32), 0, unanswered, limits)
    q = np.stack([x[0] for x in got])
    qids = np.array([x[1] for x in got], np.int32)
    g = np.stack([x[2].gids for x in got])
    rd, rg = ref.topk(q, qids, live)
    # every row below ref.n was inserted: one not live was deleted
    inr = (g >= 0) & (g < ref.n)
    deleted_hits = int((inr & ~live[np.where(inr, g, 0)]).sum())
    log(f"compare: {int((rg != check.IMAX).sum())} reference ranks filled "
        f"over {len(rg)} answered queries")
    return check.compare(np.stack([x[2].dists for x in got]), g,
                         ref.dists(q, g), rd, rg, deleted_hits, unanswered,
                         limits)


# ---------------------------------------------------------------------------
# A run
# ---------------------------------------------------------------------------

def find_cell(bench: dict, workload: str) -> tuple[dict, dict]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfgs = {c["name"]: c for c in bench["configs"]}
    return cell, cfgs[cell["config"]]


def metric_specs(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics this cell reports in this kind of run."""
    specs = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in specs if workload in m.get("workloads", [workload])]


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             process_start: float, require_tpu: bool = True,
             root: str = ROOT, overrides: Optional[dict] = None) -> dict:
    """One run; returns the result object (the last stdout line).

    ``overrides`` replaces entries of the configuration ("config") and of
    the mix ("traffic"); the tests use it to run a cell at a tiny size.
    """
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cell, cfg_entry = find_cell(bench, workload)
    over = overrides or {}
    config = merged(load_json(os.path.join(root, cfg_entry["file"])),
                    over.get("config"))
    chips = int(cell["chips"])

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"no TPU: JAX sees {dev.platform!r} devices")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}; cell {workload} seed={seed} "
        f"seconds={seconds} trace={int(trace)}")

    from repro.core import DistributedLSHIndex, LSHConfig, Scheme
    from repro.compat import make_mesh
    from repro.serving import AsyncLSHService

    from bench.data import Dataset
    from bench.reference.lsh import LSHSpec, Reference
    from bench.traffic import Traffic, load_mix

    mix = merged(load_mix(cell["traffic"]), over.get("traffic"))
    spec = LSHSpec.from_config(config)
    traffic = Traffic(mix, spec.d, spec.r, seed)
    readers = {m["name"]: importlib.import_module(f"bench.metrics.{m['name']}")
               for m in metric_specs(bench, workload, trace)}

    compiles: list = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, dur, **kw: compiles.append(time.perf_counter())
        if ev == COMPILE_EVENT else None)

    # ---- set-up: data, build, compact, set-up writes, warm-up ----------
    t = time.perf_counter()
    dataset = Dataset(config["dataset"])
    n = int(config["points"])
    extra = int(traffic.setup.get("insert", 0))
    rows = dataset.rows(n + extra)
    log(f"set-up: {n + extra} rows of {spec.d}-d {dataset.kind} data made "
        f"in {time.perf_counter() - t:.2f}s")

    ix = config["index"]
    lcfg = LSHConfig(d=spec.d, k=spec.k, W=spec.W, r=spec.r, c=spec.c,
                     L=spec.L, n_shards=chips, n_tables=spec.n_tables,
                     scheme=Scheme(ix["scheme"]), seed=spec.hash_seed)
    mesh = make_mesh((chips,), ("shard",), devices=devices[:chips])
    idx = DistributedLSHIndex(lcfg, mesh, use_kernel=True, k_neighbors=spec.K)
    t = time.perf_counter()
    drops = idx.build(rows[:n]).drops
    idx.compact()
    jax.block_until_ready(idx.store.x)
    log(f"set-up: build + compact of {n} rows in "
        f"{time.perf_counter() - t:.2f}s, drops={drops}, layout {idx.layout}")

    run = Run(device_kind=dev.device_kind)
    annotate = jax.profiler.TraceAnnotation
    svc = AsyncLSHService(idx, bucket_size=traffic.bucket,
                          k_neighbors=spec.K)
    try:
        t = time.perf_counter()
        live = _set_up_writes(svc, traffic, rows, n, extra)
        traffic.set_live(live, n)
        warm = []
        for _ in range(traffic.outstanding):   # warm the query shapes
            q, _ = traffic.plant(rows, warm=True)
            warm += svc.submit_batch(q)
        for h in warm:
            h.result(timeout=RESULT_TIMEOUT_S * 10)
        log(f"set-up: writes and warm-up in {time.perf_counter() - t:.2f}s, "
            f"layout {idx.layout}")

        # ---- the window --------------------------------------------------
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        run.t0 = time.perf_counter()
        run.setup_s = run.t0 - process_start
        with annotate("bench.window"):
            _closed_loop(svc, traffic, rows, seconds, run, annotate)
            run.t1 = time.perf_counter()
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices[:chips])
        if trace:
            jax.profiler.stop_trace()
            from bench import trace as tr
            run.events = tr.record(trace_dir)
            shutil.rmtree(trace_dir, ignore_errors=True)
    finally:
        svc.close()
    n_compiles = sum(run.t0 <= c <= run.t1 for c in compiles)
    log(f"window: {run.window_s:.3f}s, {run.queries} queries in "
        f"{len(run.batches)} batches, {n_compiles} programs compiled or "
        f"loaded in the window, peak {peak} bytes")

    # ---- the comparison, after the program's state is freed -------------
    del svc, idx
    gc.collect()
    t = time.perf_counter()
    run.reference = Reference(spec, rows)
    run.set_up_live = live
    verdict = _compare(run, live, config["limits"])
    log(f"reference: {run.queries} queries over {run.reference.n} rows in "
        f"{time.perf_counter() - t:.2f}s (after the window; not part of "
        f"set-up)")

    # ---- metrics and the result line ------------------------------------
    metrics = {}
    for m in metric_specs(bench, workload, trace):
        v = readers[m["name"]].read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": verdict["correct"],
              "attempted": run.queries,
              "failed": verdict["checks"]["unanswered"]["value"],
              "metrics": metrics, "device": device}
    if trace:
        from bench import trace as tr
        w0, w1 = tr.window(run.events)
        device["busy_s"] = tr.busy_ns(run.events, w0, w1) / 1e9
        device["window_s"] = (w1 - w0) / 1e9
        result["breakdown"] = {"device_ops": tr.top_ops(run.events, w0, w1),
                               "idle_gaps": tr.idle_gaps(run.events, w0, w1)}
    # last key: each compared number beside its limit (inf shown as 1e30)
    result["checks"] = {k: {"value": min(v["value"], 1e30),
                            "limit": v["limit"]}
                        for k, v in verdict["checks"].items()}
    return result
