"""BENCHMARK.json and the files it names agree with each other: a cell,
a mix or a metric is added by files and an entry, never by code."""
import json
import os
import re

import pytest

from conftest import ROOT

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
BENCH = os.path.join(ROOT, "bench")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]


@pytest.mark.parametrize("cell", SPEC["workloads"],
                         ids=lambda c: c["name"])
def test_cell_files_exist(cell):
    tr = json.load(open(os.path.join(BENCH, "traffic",
                                     cell["traffic"] + ".json")))
    assert os.path.isfile(os.path.join(BENCH, "drivers",
                                       tr["driver"] + ".py"))
    cfg = {c["name"]: c for c in SPEC["configs"]}[cell["config"]]
    assert json.load(open(os.path.join(ROOT, cfg["file"])))["name"] == \
        cfg["name"]
    e2e = [m for m in SPEC["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert any(cell["name"] in m["workloads"] for m in SPEC["per_layer"])


@pytest.mark.parametrize("metric", SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_file_matches_entry(metric):
    f = json.load(open(os.path.join(BENCH, "metrics",
                                    metric["name"] + ".json")))
    for k, v in metric.items():
        assert f[k] == v, k
    assert os.path.isfile(os.path.join(BENCH, "reducers",
                                       f["reducer"] + ".py"))
    moved = {m["name"]: m for m in SPEC["end_to_end"]}[metric["moves"]]
    assert set(metric["workloads"]) <= set(moved.get("workloads", []))


def test_names_and_bounds():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
