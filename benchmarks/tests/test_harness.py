"""The harness end to end, every cell, on the CPU backend at a tiny
size. The size is cut by this test's `scale`, not by the cell files."""

import json

import pytest

from benchmarks import run
from benchmarks.tests.conftest import ROOT  # noqa: F401

with open(f"{ROOT}/BENCHMARK.json") as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
SCALE = {"actors": 2048}


def last_line(capsys) -> dict:
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(cell, trace, capsys):
    rc = run.main(["--workload", cell, "--seed", "5", "--seconds", "1",
                   "--trace", str(trace), "--platform", "cpu"], scale=SCALE)
    assert rc == 0
    result = last_line(capsys)
    assert set(result) - {"breakdown"} == {"correct", "attempted", "failed",
                                           "metrics", "device"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"      # and says so
    kind = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m for m in BENCH[kind]
                if cell in m.get("workloads", [cell])}
    assert set(result["metrics"]) <= set(declared)
    for name, m in result["metrics"].items():
        assert m["unit"] == declared[name]["unit"]
        assert isinstance(m["value"], float)
    if trace:
        assert result["metrics"]["compiles_in_window"]["value"] == 0
    else:
        assert set(result["metrics"]) == set(declared)
        assert all(m["value"] > 0 for m in result["metrics"].values())


def test_no_tpu_no_result(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    captured = capsys.readouterr()
    assert rc == 2 and captured.out == ""
    assert "no result" in captured.err


def test_unknown_cell_no_result(capsys):
    rc = run.main(["--workload", "no-such.cell", "--seed", "1",
                   "--seconds", "1", "--trace", "0", "--platform", "cpu"])
    assert rc == 2 and capsys.readouterr().out == ""
