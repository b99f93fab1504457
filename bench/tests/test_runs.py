"""Whole runs of the cell at a tiny size on the CPU (interpret-mode
kernels), the look for a chip skipped: sound runs come out correct, and a
run with the timed path broken underneath comes out not correct."""
import time

import jax.numpy as jnp
import pytest

from bench import control, harness
from repro.core.index import IMAX, INF, DistributedLSHIndex

CELL = "random1m.search"
TINY = {"config": {"points": 2048},
        "traffic": {"bucket": 8, "setup": {
            "insert": 128, "delete": 16, "delete_from_insert": 4}}}


def tiny_run(seconds=2.0, trace=False, seed=2**31 + 11):
    return harness.run_cell(CELL, seed, seconds, trace, require_tpu=False,
                            process_start=time.perf_counter(),
                            overrides=TINY)


def test_a_run_needs_the_chip():
    with pytest.raises(harness.NoChip):
        harness.run_cell(CELL, 1, 1.0, False,
                         process_start=time.perf_counter())


@pytest.mark.parametrize("trace", [False, True])
def test_sound_run_is_correct(trace):
    r = tiny_run(trace=trace)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert ("setup_s" in r["metrics"]) != trace


def _state_unchanged(monkeypatch):
    orig = DistributedLSHIndex._make_insert_fn

    def make(self, *a):
        fn = orig(self, *a)

        def step(x, g, valid, *store):
            kept = tuple(jnp.copy(a) for a in store)   # inputs are donated
            return kept + tuple(fn(x, g, valid, *store)[6:])
        return step
    monkeypatch.setattr(DistributedLSHIndex, "_make_insert_fn", make)


def _delete_unchanged(monkeypatch):
    orig = DistributedLSHIndex._make_delete_fn

    def make(self, *a):
        fn = orig(self, *a)

        def step(gids, sv, sg):
            kept = jnp.copy(sv)                          # sv is donated
            return (kept,) + tuple(fn(gids, sv, sg)[1:])
        return step
    monkeypatch.setattr(DistributedLSHIndex, "_make_delete_fn", make)


def _returned(monkeypatch, alter):
    orig = DistributedLSHIndex.query_return

    def ret(self, scanned):
        d, g, e = orig(self, scanned)
        return alter(d, g) + (e,)
    monkeypatch.setattr(DistributedLSHIndex, "query_return", ret)


def _half_batch(monkeypatch):
    def alter(d, g):
        h = d.shape[0] // 2
        return d.at[h:].set(INF), g.at[h:].set(IMAX)
    _returned(monkeypatch, alter)


def _answer_altered(monkeypatch):
    _returned(monkeypatch, lambda d, g: (d, g.at[1, 0].add(1)))


@pytest.mark.parametrize("fault", [
    _state_unchanged, _delete_unchanged, _half_batch, _answer_altered])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    r = tiny_run()
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("name", ["high", "high-distances"])
def test_control_reads_above_sound_runs(name):
    """Each control (bench/control.py) at a size a test can hold reads
    well above a stand-in for a sound program (the reference in float32,
    distances as its product gives them); PERF.md gives the readings at
    the cell's size on the chip."""
    over = {"config": {"points": 20000}}
    sound, ctl = [
        [r["checks"]["rank_dist_gap"]["value"]
         for r in control.readings(CELL, [1, 2, 3], 512, [c], over)]
        for c in ("float32", name)]
    assert min(ctl) > 3 * max(sound), (sound, ctl)
