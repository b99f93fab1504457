"""The top-100 cell (``random1m-k100.search``) at a tiny size on the CPU
(interpret-mode kernels), the look for a chip skipped: a sound run is
correct and reports what the cell asks for, and both controls read well
above a stand-in for a sound program."""
import time

import pytest

from bench import control, harness
from bench.tests.test_runs import TINY

CELL = "random1m-k100.search"


@pytest.mark.parametrize("trace", [False, True])
def test_sound_top100_run_is_correct(trace):
    r = harness.run_cell(CELL, 2**31 + 29, 2.0, trace, require_tpu=False,
                         process_start=time.perf_counter(), overrides=TINY)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    want = {"qps", "setup_s"} if not trace else set()
    assert set(r["metrics"]) == want     # the CPU trace has no device ops
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("name", ["high", "high-distances"])
def test_top100_control_reads_above_sound_runs(name):
    """As ``test_runs.test_control_reads_above_sound_runs``, with all 100
    ranks compared; PERF.md gives the readings the limit was set from."""
    over = {"config": {"points": 20000}}
    sound, ctl = [
        [r["checks"]["rank_dist_gap"]["value"]
         for r in control.readings(CELL, [1, 2, 3], 512, [c], over)]
        for c in ("float32", name)]
    assert min(ctl) > 3 * max(sound), (sound, ctl)
