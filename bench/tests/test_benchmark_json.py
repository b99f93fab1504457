"""BENCHMARK.json and the files it names, held to the benchmark contract."""
import importlib
import json
import os
import re

from bench.tests.conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_metric_has_a_reader():
    b = bench()
    for m in b["end_to_end"] + b["per_layer"]:
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        assert callable(mod.read), m["name"]


def test_cells_name_files_that_exist():
    b = bench()
    cfgs = {c["name"]: c for c in b["configs"]}
    for w in b["workloads"]:
        assert w["config"] in cfgs
        assert os.path.exists(os.path.join(ROOT, "bench", "traffic",
                                           f"{w['traffic']}.json"))
    for c in b["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert set(cfg["limits"]) == {"rank_dist_gap"}


def test_names_and_fields():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert all(NAME.match(n) for n in names)
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and all(0.01 <= m["bound"] <= 0.25
                                    for m in e2e.values())
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", [w])
    for w in cells:   # every cell reports setup_s, another e2e, a layer
        assert sum(w in m.get("workloads", [w]) for m in e2e.values()) >= 2
        assert any(w in m.get("workloads", [w]) for m in b["per_layer"])


def test_check_fits_the_drivers_time():
    rs = bench()["run_seconds"]
    assert 1 <= rs <= 51
    assert 24 * 2 * 90 + (2 + 14 * 24) * (rs + 60) + 1200 <= 43200
