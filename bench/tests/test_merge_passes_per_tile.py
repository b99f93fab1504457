"""``merge_passes_per_tile`` on records built by hand, and against a
program that does not count merge passes."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench.metrics import merge_passes_per_tile
from bench.tests.test_program_metrics import run_of
from bench.tests.test_scan_us_per_tile import EVENTS
from repro.serving import BatchRecord


def rec(seq, grid_steps, merge_passes, *, columns=9):
    counters = [1, 9, 8, 30, 1024, 500, 400, grid_steps,
                merge_passes][:columns]
    return BatchRecord(
        seq=seq, reason="full", take=64, pad=0, drops=0, routed_rows=64,
        admission_wait_s=0.0, t_enqueue=0.0, t_enqueued=0.0,
        counters=np.array([counters], np.int32))


def test_passes_over_grid_steps_of_the_steady_state():
    # EVENTS holds three scan programs, one for each batch
    run = run_of([rec(0, 10, 900), rec(1, 25, 5), rec(2, 40, 8)], EVENTS)
    # batch 0 fills the closed loop and is left out
    assert merge_passes_per_tile.read(run) == pytest.approx(13 / 65)
    run = run_of([rec(0, 10, 900), rec(1, 25, 0), rec(2, 40, 0)], EVENTS)
    assert merge_passes_per_tile.read(run) == 0.0


def test_reads_nothing_where_nothing_was_counted():
    # records kept from a program that counted eight columns
    old = [rec(i, 10, 0, columns=8) for i in range(3)]
    assert old[1].merge_passes == 0
    assert merge_passes_per_tile.read(run_of(old, EVENTS)) is None
    # the jnp oracle runs no grid step
    assert merge_passes_per_tile.read(run_of(
        [rec(i, 0, 0) for i in range(3)], EVENTS)) is None
    assert merge_passes_per_tile.read(run_of([], EVENTS)) is None


def test_reads_nothing_without_the_device_trace_of_each_batch():
    recs = [rec(0, 10, 900), rec(1, 25, 5), rec(2, 40, 8)]
    assert merge_passes_per_tile.read(run_of(recs, None)) is None
    # a batch with no scan program to pair (a trace of no device)
    assert merge_passes_per_tile.read(run_of(
        recs + [rec(3, 40, 8)], EVENTS)) is None
    host_only = [e for e in EVENTS if not e["plane"].startswith("/device")]
    assert merge_passes_per_tile.read(run_of(recs, host_only)) is None


def test_reads_nothing_from_a_program_without_the_count(monkeypatch):
    """The program before the count: its records have no merge passes
    and its scan counters no such name."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "SCAN_COUNTERS", ops.SCAN_COUNTERS[:-1])
    recs = [SimpleNamespace(seq=i, counters=np.zeros((1, 8), np.int32),
                            grid_steps=10) for i in range(3)]
    assert merge_passes_per_tile.read(run_of(recs, EVENTS)) is None
