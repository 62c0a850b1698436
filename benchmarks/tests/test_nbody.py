"""The n-body cell end to end on the CPU backend at a tiny size, with
both `--trace` values, and its two layer metrics on recorded contexts.
(The references and the world tick by tick are tier-1's:
`tests/test_nbody_jovian.py`.)"""

import importlib
import json

import pytest

from benchmarks import phase_trace, run
from benchmarks.tests.conftest import ROOT

CELL = "nbody-jovian.orbit"
SCALE = {"actors": 64 * 5}          # 64 systems; the five is the source's
NEW = ("dispatch_body_ms", "delivery_word_ns")


def reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}").read


def last_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_is_correct(trace, capsys):
    with open(f"{ROOT}/BENCHMARK.json") as f:
        bench = json.load(f)
    rc = run.main(["--workload", CELL, "--seed", str(2**31 + 51),
                   "--seconds", "1", "--trace", str(trace),
                   "--platform", "cpu"], scale=SCALE)
    assert rc == 0
    result = last_line(capsys)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    got = result["metrics"]
    if not trace:
        assert set(got) == {"msgs_per_s", "setup_s"}
        assert got["msgs_per_s"]["value"] > 0
        return
    declared = {m["name"]: m for m in bench["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    assert set(got) <= set(declared)
    assert got["compiles_in_window"]["value"] == 0
    # the CPU's trace holds no device plane: the two new metrics, both
    # device shares, are left out and nothing raises
    assert not set(NEW) & set(got)
    assert {"tick_ms", "setup_build_s", "host_gap_pct"} <= set(got)


def test_the_cell_is_not_correct_when_a_tolerance_is_missed(monkeypatch,
                                                            capsys):
    """`correct` carries the tolerances: held to a position tolerance
    under float32's own rounding, the same run is refused."""
    from benchmarks import reference_nbody as ref
    monkeypatch.setattr(ref, "POS_TOL", 1e-12)
    rc = run.main(["--workload", CELL, "--seed", "7", "--seconds", "0.2",
                   "--trace", "0", "--platform", "cpu"], scale=SCALE)
    assert rc == 0
    result = last_line(capsys)
    assert result["correct"] is False and result["failed"] > 0


def test_readers_on_a_trace_with_and_without_the_scopes(monkeypatch):
    """`dispatch_body_ms` reads the Body cohort's own scope and what lies
    below it; `delivery_word_ns` is the delivery phase a tick over the
    words a tick delivers. A program that names no cohort's share leaves
    the first out; no trace at all leaves both out."""
    def device(body):
        return [[["fusion.1", 0.0, 6000.0,
                  f"jit(multi)/while/body/pony/dispatch/{body}"
                  "cond/branch_1_fun/while/body/sqrt"],
                 ["fusion.2", 6000.0, 2000.0,
                  f"jit(multi)/while/body/pony/dispatch/{body}"
                  "cond/branch_1_fun/pony/drain/select_n"],
                 ["fusion.3", 8000.0, 500.0,
                  "jit(multi)/while/body/pony/dispatch/add"],
                 ["fusion.4", 8500.0, 12000.0,
                  "jit(multi)/while/body/pony/delivery/cond/branch_1_fun/"
                  "pony/delivery/rebuild/gather"]]]
    host = [["segment", 0.0, 21000.0, None, None]]
    shape = {"messages": 1000, "record_words": 6}
    ctx = {"trace": {"ticks": 2}, "window": {}, "tick_shape": shape}
    named = phase_trace.reduce(
        {"device": device("pony/dispatch/cohort/Body/"), "host": host}, 2)
    monkeypatch.setattr(phase_trace, "of_run", lambda _ctx: named)
    assert reader("dispatch_body_ms")(ctx) == pytest.approx(3e-3)
    # 12,000 ns in 2 ticks = 6,000 ns a tick over 6,000 words
    assert reader("delivery_word_ns")(ctx) == pytest.approx(1.0)
    assert reader("phase_dispatch_ms")(ctx) == pytest.approx(4.25e-3)
    parents = phase_trace.reduce({"device": device(""), "host": host}, 2)
    monkeypatch.setattr(phase_trace, "of_run", lambda _ctx: parents)
    assert reader("dispatch_body_ms")(ctx) is None
    assert reader("delivery_word_ns")(ctx) == pytest.approx(1.0)
    monkeypatch.setattr(phase_trace, "of_run", lambda _ctx: None)
    for name in NEW:
        assert reader(name)(ctx) is None, name


def test_every_new_metric_is_declared_for_the_cell():
    with open(f"{ROOT}/BENCHMARK.json") as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert declared[name]["moves"] == "msgs_per_s"
        assert declared[name]["workloads"] == [CELL]
        assert callable(reader(name))
    assert declared["dispatch_body_ms"]["layer"] == "window / tick"
    assert declared["delivery_word_ns"]["layer"] == "formulations / kernels"
    # every per-layer metric the one-chip throughput cells report, and
    # the whole tick's share of its roofline
    for m in bench["per_layer"]:
        cells = m.get("workloads", [])
        if "ubench-1m.cycle" in cells and "ubench-1m.sparse" in cells:
            assert CELL in cells, m["name"]
    assert CELL in declared["tick_roofline"]["workloads"]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["msgs_per_s"]["workloads"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "orbit")
