"""Tests for the static SPMD contract analyzer (repro.analysis).

Three layers, mirroring the passes:

  * jaxpr pass on hand-built toy jaxprs -- collective counting through
    nested pjit/shard_map (including the psum->psum2 primitive rename),
    rogue-collective detection, flatness, intermediate ceilings, 64-bit
    drift (with the PRNG-key exemption);
  * HLO pass on synthetic module headers and tiny real compiles --
    donation alias/donor parsing with nested braces, memory budgets,
    VMEM envelope budgets;
  * repolint on a fixture tree exercising every rule both ways, plus a
    clean self-scan of the actual repo;
  * the ``python -m repro.analysis.check`` gate end-to-end in a
    subprocess: exit 0 on main, nonzero for every seeded violation
    class (the compile-heavy classes are nightly/slow).
"""
from collections import Counter
import json
import os
import subprocess
import sys

import pytest

from repro.analysis import hlo_pass, jaxpr_pass, load_contracts, repolint
from repro.analysis.manifest import flatness_ratio, repo_root

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_FIXTURES = os.path.join(_REPO, "tests", "fixtures", "repolint")

CONTRACTS = load_contracts()


# ---------------------------------------------------------------------------
# jaxpr pass: toy jaxprs
# ---------------------------------------------------------------------------

def _one_dev_mesh():
    from repro.compat import make_mesh
    return make_mesh((1,), ("shard",))


def _shmap(fn, out_specs):
    import jax
    from jax.sharding import PartitionSpec as P
    return jax.jit(jax.shard_map(fn, mesh=_one_dev_mesh(),
                                 in_specs=(P("shard"),), out_specs=out_specs,
                                 check_vma=False))


def test_collective_counts_sees_psum_despite_rename():
    """jax renamed the traced primitive psum -> psum2; the structural
    counter must normalize it (the old \\bpsum\\b regex counted zero)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    f = _shmap(lambda x: jax.lax.psum(x, "shard"), P())
    cj = jax.make_jaxpr(f)(jnp.ones((4,), jnp.float32))
    counts = jaxpr_pass.collective_counts(cj)
    assert counts.get("psum") == 1, counts
    # and it is found structurally even though it sits inside pjit(...)
    names = {e.primitive.name for e in jaxpr_pass.iter_eqns(cj)}
    assert "psum" in names or "psum2" in names


def test_rogue_all_gather_fails_query_budget():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    f = _shmap(lambda x: jax.lax.all_gather(x, "shard", axis=0, tiled=True),
               P())
    cj = jax.make_jaxpr(f)(jnp.ones((4,), jnp.float32))
    counts = jaxpr_pass.collective_counts(cj)
    assert counts.get("all_gather") == 1, counts
    viol = jaxpr_pass.check_collectives(
        counts, CONTRACTS["jaxpr"]["collectives"]["query_dispatch"], "toy")
    assert viol and "all_gather" in viol[0]


def test_unbudgeted_collective_kind_fails_closed():
    """A collective kind absent from the budget has an implicit budget
    of zero -- new primitives cannot slip past a fixed allowlist."""
    viol = jaxpr_pass.check_collectives({"ppermute": 1}, {"all_to_all": 2})
    assert any("ppermute" in v for v in viol)
    # exact match: too FEW is also a violation (the fused a2a vanished)
    viol = jaxpr_pass.check_collectives({}, {"all_to_all": 2})
    assert any("all_to_all" in v for v in viol)


def test_eqn_count_recurses_into_nested_pjit():
    import jax
    import jax.numpy as jnp

    inner = jax.jit(lambda x: jnp.sin(x) + jnp.cos(x))
    outer = jax.jit(lambda x: inner(x) * 2.0)
    cj = jax.make_jaxpr(outer)(jnp.ones((4,), jnp.float32))
    # must see through both pjit layers: sin, cos, add, mul at least
    assert jaxpr_pass.eqn_count(cj) >= 4


def test_intermediate_ceiling_catches_big_matrix():
    import jax
    import jax.numpy as jnp

    def blowup(q, x):
        # the O(R*N) pattern the kernel exists to avoid
        return jnp.einsum("rd,nd->rn", q, x).min(axis=1)

    cj = jax.make_jaxpr(blowup)(jnp.ones((512, 8), jnp.float32),
                                jnp.ones((512, 8), jnp.float32))
    rep = jaxpr_pass.analyze_phase(cj, "delete", 1, CONTRACTS)
    assert rep["max_intermediate"]["numel"] == 512 * 512
    assert any("ceiling" in v for v in rep["violations"])


def test_wide_dtype_drift_flagged_but_prng_keys_exempt():
    import jax
    import jax.numpy as jnp

    def key_fn():
        return jax.random.fold_in(jax.random.key(0), 7)

    stats = jaxpr_pass.intermediate_stats(jax.make_jaxpr(key_fn)())
    assert stats["wide_dtypes"] == [], stats  # key<fry> itemsize 8: exempt

    def wide_fn():
        return jnp.arange(8, dtype=jnp.int64) * 2

    with jax.enable_x64(True):
        stats = jaxpr_pass.intermediate_stats(jax.make_jaxpr(wide_fn)())
    assert stats["wide_dtypes"], "int64 intermediate must be flagged"


def test_flatness_check():
    ratio = flatness_ratio(CONTRACTS)
    assert jaxpr_pass.check_flatness({1: 800, 2: 804, 4: 806}, ratio) == []
    viol = jaxpr_pass.check_flatness({1: 800, 4: 1600}, ratio,
                                     "query_dispatch")
    assert viol and "not flat" in viol[0]


# ---------------------------------------------------------------------------
# HLO pass: header parsing + tiny real compiles
# ---------------------------------------------------------------------------

_HEADER = ("HloModule jit_insert, input_output_alias={ {0}: (3, {}, "
           "may-alias), {1}: (4, {}, may-alias), {5}: (8, {}, may-alias) }, "
           "entry_computation_layout={(f32[8,4])->f32[8,4]}")
_DONOR_HEADER = ("HloModule jit_query, buffer_donor={ (0, {}) }, "
                 "entry_computation_layout={(f32[8,4])->f32[4]}")


def test_alias_parser_handles_nested_braces():
    # the {} inside each entry must not terminate the block early
    assert hlo_pass.aliased_params(_HEADER) == {3, 4, 8}
    assert hlo_pass.donor_params(_HEADER) == set()
    assert hlo_pass.donor_params(_DONOR_HEADER) == {0}
    assert hlo_pass.aliased_params("HloModule bare") == set()


def test_donation_report_negative_on_undonated_buffer():
    rep = hlo_pass.donation_report("HloModule bare", "query_dispatch",
                                   CONTRACTS)
    assert rep["violations"] and "copied" in rep["violations"][0]
    rep = hlo_pass.donation_report(_DONOR_HEADER, "query_dispatch",
                                   CONTRACTS)
    assert rep["violations"] == []
    # insert requires the six store columns actually aliased
    rep = hlo_pass.donation_report(_HEADER, "insert", CONTRACTS)
    assert rep["violations"] and "6" in rep["violations"][0]


def test_real_compile_donation_roundtrip():
    import jax
    import jax.numpy as jnp

    donating = jax.jit(lambda x: x + 1.0, donate_argnums=(0,))
    text = donating.lower(jnp.ones((128,), jnp.float32)).compile().as_text()
    assert hlo_pass.aliased_params(text) | hlo_pass.donor_params(text)

    plain = jax.jit(lambda x: x + 1.0)
    text = plain.lower(jnp.ones((128,), jnp.float32)).compile().as_text()
    assert not (hlo_pass.aliased_params(text) | hlo_pass.donor_params(text))


def test_memory_report_budget():
    import jax
    import jax.numpy as jnp

    compiled = jax.jit(lambda x: (x @ x.T).sum(axis=0)).lower(
        jnp.ones((64, 64), jnp.float32)).compile()
    ok = hlo_pass.memory_report(compiled, "insert", CONTRACTS)
    assert not ok["violations"], ok
    tight = json.loads(json.dumps(CONTRACTS))
    tight["hlo"]["temp_bytes_ceiling"]["insert"] = 1
    bad = hlo_pass.memory_report(compiled, "insert", tight)
    if "temp_bytes" in bad:  # backend supports memory_analysis
        assert bad["violations"], bad


def test_vmem_envelope_budget():
    rep = hlo_pass.vmem_report(CONTRACTS)
    assert rep["violations"] == [], rep
    assert rep["bucket_search_bytes"] > 0
    tight = json.loads(json.dumps(CONTRACTS))
    tight["vmem"]["budget_bytes"] = 1
    assert hlo_pass.vmem_report(tight)["violations"]


# ---------------------------------------------------------------------------
# repolint: fixture tree, both ways
# ---------------------------------------------------------------------------

LINT_CFG = CONTRACTS["repolint"]


def _fixture_violations(name):
    return repolint.scan_files([os.path.join(_FIXTURES, name)], LINT_CFG,
                               rel_root=_FIXTURES)


def test_repolint_clean_fixture_has_no_violations():
    assert _fixture_violations("clean.py") == []


def test_repolint_bad_fixture_trips_every_rule():
    viol = _fixture_violations("bad.py")
    by_rule = {}
    for v in viol:
        by_rule.setdefault(v.rule, []).append(v)
    assert len(by_rule.get("host-sync", [])) == 2, viol
    assert len(by_rule.get("deprecated-shim", [])) == 2, viol
    assert len(by_rule.get("kw-only-kernel-api", [])) == 2, viol
    assert len(by_rule.get("store-mutation", [])) == 2, viol
    # exactly these -- no accidental extra rules firing on the fixture
    assert len(viol) == 8, viol


def test_repolint_hot_module_scope():
    src = "import numpy as np\ndef helper(x):\n    return np.asarray(x)\n"
    # same code: hot inside kernels/, fine elsewhere
    hot = repolint.lint_source(src, "src/repro/kernels/util.py", LINT_CFG)
    assert [v.rule for v in hot] == ["host-sync"]
    cold = repolint.lint_source(src, "src/repro/serving/util.py", LINT_CFG)
    assert cold == []
    # module level in a hot module is setup, not a traced step
    top = repolint.lint_source("import numpy as np\nA = np.asarray([1])\n",
                               "src/repro/kernels/util.py", LINT_CFG)
    assert top == []


def test_repolint_allowlists_respected():
    src = "def f(idx):\n    return idx.table_params\n"
    assert repolint.lint_source(src, "src/repro/core/index.py", LINT_CFG) == []
    assert repolint.lint_source(src, "src/repro/launch/x.py", LINT_CFG)


def test_repolint_repo_is_clean():
    """The actual repo passes its own lint (the same scan the gate runs)."""
    report = repolint.scan(repo_root(), LINT_CFG)
    assert report["files_scanned"] > 50
    assert report["violations"] == [], report["violations"]


# ---------------------------------------------------------------------------
# the gate end-to-end (subprocess; check.py configures its own devices)
# ---------------------------------------------------------------------------

def _run_check(tmp_path, *extra, timeout=900):
    out_json = os.path.join(str(tmp_path), "report.json")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    env.pop("XLA_FLAGS", None)  # check.py must set this itself
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis.check", "--json", out_json,
         *extra],
        capture_output=True, text=True, env=env, timeout=timeout,
        cwd=_REPO)
    report = None
    if os.path.exists(out_json):
        with open(out_json) as f:
            report = json.load(f)
    return proc, report


def test_check_seeded_host_sync_fails_fast(tmp_path):
    """--skip-compile keeps this in the fast unit tier: the seeded
    hot-path host sync must fail the gate."""
    proc, report = _run_check(tmp_path, "--seed-violation", "host-sync",
                              "--skip-compile", timeout=120)
    assert proc.returncode != 0, proc.stdout + proc.stderr
    assert report is not None and not report["ok"]
    assert any(v["rule"] == "host-sync"
               for v in report["repolint"]["violations"])
    # unseeded skip-compile run is clean
    proc, report = _run_check(tmp_path, "--skip-compile", timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert report["ok"]


@pytest.mark.multidevice
def test_check_passes_on_main(tmp_path):
    """The full gate (real insert/delete steps and the query's three
    staged steps at T in {1,2,4}, 8 host devices) holds on the current
    tree; the query's two all_to_all are its dispatch's and its
    return's."""
    proc, report = _run_check(tmp_path)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert report["ok"] and report["violations"] == []
    ph = report["jaxpr"]["phases"]
    for T in ("1", "2", "4"):
        assert ph["insert"][T]["collectives"] == {"all_to_all": 1}
        assert ph["delete"][T]["collectives"] == {}
        stages = [ph[f"query_{s}"][T]["collectives"]
                  for s in ("dispatch", "scan", "return")]
        assert stages == [{"all_to_all": 1}, {}, {"all_to_all": 1}]
        assert sum(map(Counter, stages), Counter()) == {"all_to_all": 2}
    assert report["hlo"]["donation"]["insert"]["aliased_params"]
    don = report["hlo"]["donation"]["query_dispatch"]
    assert don["aliased_params"] or don["donor_params"]


@pytest.mark.slow
@pytest.mark.multidevice
@pytest.mark.parametrize("seed", ["extra-collective", "broken-donation",
                                  "jaxpr-growth"])
def test_check_seeded_violations_fail(tmp_path, seed):
    """Each compile-level seeded violation class must fail the gate with
    a violation naming its contract."""
    proc, report = _run_check(tmp_path, "--seed-violation", seed)
    assert proc.returncode != 0, (seed, proc.stdout, proc.stderr)
    assert report is not None and not report["ok"]
    needle = {"extra-collective": "all_gather",
              "broken-donation": "donate",
              "jaxpr-growth": "not flat"}[seed]
    assert any(needle in v for v in report["violations"]), report["violations"]
