"""BENCHMARK.json against the contract's limits that can be checked
without a chip, and against the files it names."""

import importlib
import json
import os
import re

import pytest

from benchmarks.tests.conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level(bench):
    assert set(bench) == TOP_KEYS
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) < 64 * 1024
    assert all(line(w) for w in bench["command"])
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    # the command names no file outside `paths`
    files = [w for w in bench["command"] if "/" in w]
    assert all(w.split("/")[0] in bench["paths"] for w in files)


def test_configs(bench):
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line(c["source"]) and line(c["why"])
        assert c["file"].split("/")[0] in bench["paths"]
        assert all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert cfg["guarantees"]
        importlib.import_module(f"benchmarks.worlds.{cfg['world']}").build


def test_workloads(bench):
    cells = bench["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    configs = {c["name"] for c in bench["configs"]}
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line(w["why"])
        path = os.path.join(ROOT, "benchmarks", "traffic",
                            w["traffic"] + ".json")
        with open(path) as f:
            traffic = json.load(f)
        mode = importlib.import_module(f"benchmarks.modes.{traffic['mode']}")
        assert all(hasattr(mode, f)
                   for f in ("warm_up", "window", "traced", "finish"))


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and line(m["layer"])
        assert m["source"] in SOURCES
        reader = importlib.import_module(
            f"benchmarks.layer_metrics.{m['name']}")
        assert callable(reader.read)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    # every cell reports setup_s, another end-to-end metric and at least
    # one per-layer metric
    for cell in cells:
        mine = [m for m in bench["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert len(mine) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"])


def test_run_py_knows_no_names(bench):
    with open(os.path.join(ROOT, "benchmarks", "run.py")) as f:
        text = f.read()
    names = ([w["name"] for w in bench["workloads"]]
             + [w["traffic"] for w in bench["workloads"]]
             + [c["name"] for c in bench["configs"]]
             + [m["name"] for m in bench["per_layer"]])
    found = [n for n in names if re.search(rf"(?<![\w.\-]){re.escape(n)}"
                                           rf"(?![\w\-])", text)]
    assert not found, found


def test_files_use_name_characters(bench):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in bench["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert ok.match(rel), rel
