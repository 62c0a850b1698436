"""Per-phase numbers from a trace: the rules on hand-made data, the
reduction and every reader on a trace recorded on the v5e, and the new
readers end to end on the CPU backend."""

import collections
import gzip
import importlib
import json
import os

import pytest

from benchmarks import phase_trace, run
from benchmarks.tests.conftest import ROOT

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "recorded_phases.json.gz")
with open(f"{ROOT}/BENCHMARK.json") as f:
    BENCH = json.load(f)
NEW = ("phase_delivery_ms", "phase_dispatch_ms", "mailbox_rebuild_ms",
       "plan_rebuild_ms", "plan_miss_pct", "unscoped_pct", "host_idle_pct",
       "window_host_us", "slow_window_device_pct", "slow_window_host_pct",
       "hop_device_ops", "hop_phase_delivery_us", "hop_phase_dispatch_us",
       "hop_host_idle_pct", "hop_window_host_us")


def recorded() -> dict:
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}").read


@pytest.mark.parametrize("op_name, scope", [
    ("jit(multi)/while/body/pony/delivery/cond/branch_1_fun/pony/delivery/"
     "rebuild/jit(_take)/gather", "delivery/rebuild"),
    ("jit(multi)/while/body/pony/dispatch/cond", "dispatch"),
    ("jit(multi)/while/body/pony/dispatch/cond/branch_1_fun/pony/drain/"
     "dynamic_slice", "drain"),
    ("jit(multi)/while/body/pony/delivery/pony/delivery/plan/cond/"
     "branch_0_fun/jit(argsort)/sort", "delivery/plan"),
    ("jit(multi)/while/cond/pony/vote/and", "vote"),
    ("jit(multi)/while/body/closed_call/add", None),
    ("jit(multi)/while/body/pony", None),
    ("", None), (None, None)])
def test_scope_of(op_name, scope):
    assert phase_trace.scope_of(op_name) == scope


def test_wire_reader_reads_fields():
    # field 1 varint 300; field 2 bytes "ab"; field 3 fixed32
    message = bytes([0x08, 0xAC, 0x02, 0x12, 0x02, 0x61, 0x62,
                     0x1D, 1, 0, 0, 0])
    fields = list(phase_trace._fields(memoryview(message)))
    assert fields[0] == (1, 300)
    assert fields[1][0] == 2 and bytes(fields[1][1]) == b"ab"
    assert fields[2][0] == 3 and len(fields[2][1]) == 4


def test_self_intervals_give_each_moment_to_the_innermost_span():
    spans = [["a", 0.0, 100.0], ["b", 10.0, 20.0], ["c", 15.0, 5.0],
             ["d", 200.0, 10.0]]
    got = collections.defaultdict(float)
    for name, lo, hi in phase_trace._self_intervals(spans):
        got[name] += hi - lo
    assert got == {"a": 80.0, "b": 15.0, "c": 5.0, "d": 10.0}


def hand_made():
    """Two ticks. A `while` holds: a rebuild fusion, a plan-miss sort
    inside a conditional, an operation with no scope."""
    body = "jit(multi)/while/body/"
    device = [[
        ["while.1", 1000.0, 8000.0, None],
        ["fusion.20", 1000.0, 3000.0,
         body + "pony/delivery/cond/branch_1_fun/pony/delivery/rebuild/gather"],
        ["cond.7", 4000.0, 2000.0, body + "pony/delivery/pony/delivery/plan/"
         "cond"],
        ["sort.0", 4500.0, 1000.0, body + "pony/delivery/pony/delivery/plan/"
         "cond/branch_1_fun/sort"],
        ["copy.3", 6500.0, 500.0, None],
        ["fusion.9", 7000.0, 1000.0, body + "pony/dispatch/mul"]]]
    host = [["segment", 0.0, 10000.0, None, None],
            ["pony:dispatching", 0.0, 1000.0, 4, None],
            ["pony:wait", 1000.0, 8500.0, 4, None],
            ["pony:host-work", 9500.0, 400.0, 4, 2],
            ["pony:outbox", 9600.0, 100.0, 4, None]]
    return {"device": device, "host": host}


def test_hand_made_phases_and_spans():
    r = phase_trace.reduce(hand_made(), 2)
    s = {k: v["s"] * 1e9 for k, v in r["phases"].items()}
    assert s["delivery/rebuild"] == pytest.approx(3000)
    assert s["delivery/plan"] == pytest.approx(2000)   # cond self + sort
    assert s["dispatch"] == pytest.approx(1000)
    # the while's self time and the copy carry no scope
    assert s["unscoped"] == pytest.approx(8000 - 3000 - 2000 - 1000)
    assert sum(s.values()) == pytest.approx(r["busy_s"] * 1e9)
    assert r["plan_sorts"] == 1 and r["leaf_ops"] == 4 and r["scoped"]
    assert r["windows"] == 1
    assert r["window_host_s"] * 1e9 == pytest.approx(1000 + 400)
    idle = {k: v * 1e9 for k, v in r["idle_s"].items()}
    assert idle["pony:dispatching"] == pytest.approx(1000)
    assert idle["pony:wait"] == pytest.approx(500)
    assert idle["pony:host-work"] + idle["pony:outbox"] == pytest.approx(400)
    assert idle["outside"] == pytest.approx(100)
    assert phase_trace.under(r, "delivery") * 1e9 == pytest.approx(5000)
    text = phase_trace.table(r)
    assert "delivery/rebuild" in text and "fusion.20" in text


def test_recorded_trace_names_the_tick():
    data = recorded()
    r = phase_trace.reduce(data, data["ticks"])
    per_tick = {k: 1e3 * v["s"] / 3 for k, v in r["phases"].items()}
    # PERF.md's table for ubench-1m.random (PR 24, chip call 1)
    assert per_tick["delivery/rebuild"] == pytest.approx(410.04, abs=0.01)
    assert per_tick["delivery/plan"] == pytest.approx(322.73, abs=0.01)
    assert r["phases"]["delivery/rebuild"]["top"][0][0].startswith(
        "fusion.20 s32[67108864,2]")
    assert [n.split(" ")[0] for n, _ in r["phases"]["delivery/plan"]["top"]] \
        == ["fusion.121", "fusion.1", "sort.0"]
    assert r["plan_sorts"] == 3
    assert sum(v["s"] for v in r["phases"].values()) == \
        pytest.approx(r["busy_s"], rel=1e-9)
    assert r["busy_s"] / 3 == pytest.approx(1.0745954, rel=1e-6)
    assert per_tick["unscoped"] / (1e3 * r["busy_s"] / 3) < 0.02
    assert r["windows"] == 3
    assert sum(r["idle_s"].values()) == pytest.approx(
        r["span_s"] - r["busy_s"], rel=1e-6)


@pytest.fixture
def ctx(monkeypatch):
    data = recorded()
    reduced = phase_trace.reduce(data, data["ticks"])
    monkeypatch.setattr(phase_trace, "of_run", lambda _ctx: reduced)
    return {"trace": {"ticks": 3}, "window": {}}


def test_readers_on_the_recorded_trace(ctx):
    got = {n: reader(n)(ctx) for n in NEW if not n.startswith("slow_")}
    assert all(isinstance(v, float) for v in got.values()), got
    assert got["mailbox_rebuild_ms"] == pytest.approx(410.04, abs=0.01)
    assert got["plan_rebuild_ms"] == pytest.approx(322.73, abs=0.01)
    assert got["plan_miss_pct"] == 100.0
    assert got["phase_delivery_ms"] > got["mailbox_rebuild_ms"] \
        + got["plan_rebuild_ms"]
    assert got["hop_phase_delivery_us"] == \
        pytest.approx(1e3 * got["phase_delivery_ms"])
    assert 0 < got["unscoped_pct"] < 2
    assert 0 < got["host_idle_pct"] < 1
    assert got["window_host_us"] == pytest.approx(3387.7, abs=0.1)
    assert got["hop_device_ops"] == pytest.approx(949.33, abs=0.01)


def test_readers_on_a_program_without_scopes_or_spans(monkeypatch):
    """The parent's trace: the same operations, no op_name under
    `pony/`, no `pony:` spans. Nothing to read, nothing raised."""
    data = recorded()
    bare = {"device": [[[n, s, d, None] for n, s, d, _ in evs]
                       for evs in data["device"]],
            "host": [h for h in data["host"] if not h[0].startswith("pony:")]}
    reduced = phase_trace.reduce(bare, 3)
    assert not reduced["scoped"] and not reduced["spans"]
    monkeypatch.setattr(phase_trace, "of_run", lambda _ctx: reduced)
    ctx = {"trace": {"ticks": 3}, "window": {}}
    for name in NEW:
        if name.startswith("slow_") or name == "hop_device_ops":
            continue
        assert reader(name)(ctx) is None, name
    assert reader("hop_device_ops")(ctx) > 0    # needs no names


def window_record(ticks, since_prev, wall, wait):
    return {"ticks": ticks, "since_prev_ms": since_prev, "wall_ms": wall,
            "wait_ms": wait}


def test_slow_windows_split_by_who_had_the_clock(monkeypatch):
    from ponyc_tpu import flight
    quiet = [window_record(1, 1.0, 100.0, 97.0) for _ in range(8)]
    records = (quiet[:4]
               + [window_record(1, 1.0, 150.0, 147.0),    # the device's
                  window_record(1, 31.0, 100.0, 97.0)]    # the host's
               + quiet[4:]
               + [window_record(1, 50.0, 100.0, 97.0)] * 3)   # traced
    recorder = type("R", (), {"windows": collections.deque(records)})()
    monkeypatch.setattr(flight, "latest", lambda: recorder, raising=False)
    ctx = {"trace": {"ticks": 3},
           "window": {"run_loop_windows": 10, "ticks": 10}}
    device = reader("slow_window_device_pct")(ctx)
    host = reader("slow_window_host_pct")(ctx)
    total = 10 * 101.0 + 50.0 + 30.0
    assert device == pytest.approx(100 * 50.0 / total)
    assert host == pytest.approx(100 * 30.0 / total)
    # the ring no longer holds the whole window: nothing to say
    ctx["window"] = {"run_loop_windows": 11, "ticks": 11}
    assert reader("slow_window_device_pct")(ctx) is None
    # records from before the fields existed
    recorder.windows = collections.deque({"ticks": 1} for _ in records)
    ctx["window"] = {"run_loop_windows": 10, "ticks": 10}
    assert reader("slow_window_host_pct")(ctx) is None


def test_every_new_metric_is_declared_with_a_reader():
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        assert name in declared and callable(reader(name))
        assert declared[name]["better"] == "lower"


@pytest.mark.parametrize("cell", ["ubench-1m.random", "ring-1024.token"])
def test_new_readers_end_to_end_on_the_cpu(cell, capsys):
    """The CPU backend has no device plane: the device readers say
    nothing, the span readers read the run loop's spans."""
    rc = run.main(["--workload", cell, "--seed", "6", "--seconds", "1",
                   "--trace", "1", "--platform", "cpu"],
                  scale={"actors": 2048})
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    metrics = json.loads(out[-1])["metrics"]
    assert all(isinstance(m["value"], float) for m in metrics.values())
    span = "hop_window_host_us" if cell.startswith("ring") \
        else "window_host_us"
    assert metrics[span]["value"] > 0
    assert any(line.startswith("run-loop spans") for line in out)
    assert not set(metrics) & {"mailbox_rebuild_ms", "unscoped_pct",
                               "hop_device_ops", "host_idle_pct"}
