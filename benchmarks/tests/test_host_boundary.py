"""The readers of the host's side of the chip: a window's launch lead
and way back on hand-built `phase_trace.load()` data, the counter
readers on stand-in records and statistics, and all of them end to end
on the CPU backend."""

import collections
import importlib
import json

import pytest

from benchmarks import phase_trace, run
from benchmarks.layer_metrics import launch_lead_us
from benchmarks.tests.conftest import ROOT

with open(f"{ROOT}/BENCHMARK.json") as f:
    BENCH = json.load(f)
NEW = ("setup_build_s", "setup_cold_launch_s", "counter_read_us",
       "window_dispatch_us", "launch_lead_us", "wait_return_us",
       "hop_launch_lead_us", "hop_wait_return_us")
US = 1e3        # a microsecond, in the trace's nanoseconds


def reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}").read


def op(start_us, dur_us, name="fusion.1"):
    return [name, start_us * US, dur_us * US, None]


def span(name, start_us, dur_us, window):
    return ["pony:" + name, start_us * US, dur_us * US, window, None]


def test_a_sync_window_has_a_lead_and_a_way_back():
    """dispatch at 100 us, the first operation at 130; the last ends at
    900, the wait returns at 960."""
    data = {"device": [[op(130, 400), op(530, 370)]],
            "host": [span("dispatching", 100, 50, 7),
                     span("wait", 150, 810, 7),
                     span("host-work", 960, 20, 7)]}
    found = launch_lead_us.stretches(data)
    assert found == {"lead": [30 * US], "back": [60 * US]}


def test_a_pipelined_window_has_neither_at_its_seam():
    """Window 2 is dispatched behind window 1 and runs on while window
    1 is retired: the device idles only before window 1 and after
    window 2, and a `while` around a window's operations is no second
    operation."""
    data = {"device": [[op(130, 870, "while.1"), op(140, 400), op(560, 430),
                        op(1005, 880, "while.2"), op(1010, 870)]],
            "host": [span("dispatching", 100, 20, 1),
                     span("dispatching", 125, 20, 2),   # behind window 1
                     span("wait", 150, 900, 1),         # ends in window 2
                     span("wait", 1060, 890, 2)]}       # ... at 1950
    found = launch_lead_us.stretches(data)
    assert found == {"lead": [30 * US], "back": [(1950 - 1885) * US]}


def test_the_next_sync_dispatch_starts_after_the_retire():
    """Two windows one after the other: both are sync points, each
    pairs with its own wait by `window=`."""
    data = {"device": [[op(110, 100), op(330, 100)]],
            "host": [span("dispatching", 100, 5, 1), span("wait", 105, 125, 1),
                     span("dispatching", 300, 5, 2), span("wait", 305, 145, 2)]}
    found = launch_lead_us.stretches(data)
    assert found == {"lead": [10 * US, 30 * US], "back": [20 * US, 20 * US]}


def test_a_window_without_a_device_operation_says_nothing(monkeypatch):
    """The CPU backend's trace (no device plane), and a wait inside
    which no operation ended."""
    data = {"device": [],
            "host": [span("dispatching", 100, 5, 1), span("wait", 105, 95, 1)]}
    assert launch_lead_us.stretches(data) == {"lead": [], "back": []}
    early = {"device": [[op(0, 50)]], "host": [span("wait", 105, 95, 1)]}
    assert launch_lead_us.stretches(early) == {"lead": [], "back": []}
    monkeypatch.setattr(launch_lead_us, "of_run",
                        lambda: launch_lead_us.stretches(data))
    for name in ("launch_lead_us", "wait_return_us", "hop_launch_lead_us",
                 "hop_wait_return_us"):
        assert reader(name)({}) is None
    # ... and no trace at all
    monkeypatch.setattr(launch_lead_us, "of_run", lambda: None)
    assert reader("launch_lead_us")({}) is None


def test_the_median_over_the_windows_is_reported_in_microseconds(monkeypatch):
    monkeypatch.setattr(launch_lead_us, "of_run", lambda: {
        "lead": [10 * US, 30 * US, 500 * US], "back": [20 * US, 40 * US]})
    assert reader("launch_lead_us")({}) == 30.0
    assert reader("hop_launch_lead_us")({}) == 30.0
    assert reader("wait_return_us")({}) == 30.0
    assert reader("hop_wait_return_us")({}) == 30.0


def record(ticks, dispatch_ms=None):
    r = {"ticks": ticks, "since_prev_ms": 1.0, "wall_ms": 100.0,
         "wait_ms": 97.0}
    if dispatch_ms is not None:
        r["dispatch_ms"] = dispatch_ms
    return r


def with_recorder(monkeypatch, records, stats=None):
    from ponyc_tpu import flight
    rt = type("Rt", (), {"run_loop_stats": lambda self: stats})()
    recorder = type("R", (), {"windows": collections.deque(records),
                              "rt": rt})()
    monkeypatch.setattr(flight, "latest", lambda: recorder, raising=False)


def test_window_dispatch_is_the_timed_windows_median(monkeypatch):
    timed = [record(2, d) for d in (3.0, 2.9, 9.1, 3.1, 3.2)]
    traced = [record(2, 50.0)] * 2            # under the profiler: not read
    with_recorder(monkeypatch, [record(2, 70.0)] + timed + traced)
    ctx = {"trace": {"ticks": 4},
           "window": {"run_loop_windows": 5, "ticks": 10}}
    assert reader("window_dispatch_us")(ctx) == pytest.approx(3100.0)
    # a record ring shorter than the window: nothing to say
    ctx["window"] = {"run_loop_windows": 7, "ticks": 14}
    assert reader("window_dispatch_us")(ctx) is None
    # the parent's records have no dispatch_ms
    with_recorder(monkeypatch, [record(2)] * 8)
    ctx["window"] = {"run_loop_windows": 5, "ticks": 10}
    assert reader("window_dispatch_us")(ctx) is None
    assert reader("window_dispatch_us")({"trace": None, "window": {}}) is None


def test_setup_readers_sum_the_build_phases_and_the_cold_launch(monkeypatch):
    phase_s = {"start": 0.5, "spawn": 0.25, "set-fields": 0.125,
               "bulk-send": 0.0625, "blob-store": 1.0, "counter": 9.0,
               "read": 9.0, "dispatching": 9.0}
    with_recorder(monkeypatch, [], {"phase_s": phase_s,
                                    "cold_dispatch_s": 1.5})
    assert reader("setup_build_s")({}) == 1.9375
    assert reader("setup_cold_launch_s")({}) == 1.5
    # the parent names one of the five and marks no launch
    with_recorder(monkeypatch, [], {"phase_s": {"blob-store": 1.0,
                                                "dispatching": 9.0}})
    assert reader("setup_build_s")({}) is None
    assert reader("setup_cold_launch_s")({}) is None
    from ponyc_tpu import flight
    monkeypatch.setattr(flight, "latest", lambda: None, raising=False)
    assert reader("setup_build_s")({}) is None


def test_counter_read_is_seconds_a_call(monkeypatch):
    reduced = {"spans": {"pony:counter": {"s": 0.006, "self_s": 0.006,
                                          "n": 3}}}
    monkeypatch.setattr(phase_trace, "of_run", lambda _ctx: reduced)
    assert reader("counter_read_us")({}) == pytest.approx(2000.0)
    monkeypatch.setattr(phase_trace, "of_run",
                        lambda _ctx: {"spans": {"pony:wait": {"s": 1.0,
                                                              "n": 1}}})
    assert reader("counter_read_us")({}) is None
    monkeypatch.setattr(phase_trace, "of_run", lambda _ctx: None)
    assert reader("counter_read_us")({}) is None


def test_every_new_metric_is_declared_with_a_reader():
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    ring = {w["name"] for w in BENCH["workloads"]
            if w["name"].startswith("ring")}
    for name in NEW:
        m = declared[name]
        assert callable(reader(name)) and m["better"] == "lower"
        if name.startswith("setup_"):
            assert m["moves"] == "setup_s" and m["layer"] == "start-up"
        elif name.startswith("hop_"):
            assert m["moves"] == "hop_us" and set(m["workloads"]) == ring
        else:
            assert m["moves"] == "msgs_per_s" \
                and not set(m["workloads"]) & ring


@pytest.mark.parametrize("cell", ["ubench-1m.sparse", "ring-1024.token"])
def test_new_readers_end_to_end_on_the_cpu(cell, capsys):
    """The CPU backend's trace has the spans and no device plane: the
    counters and the span readers give numbers, the two that need the
    device's operations say nothing."""
    rc = run.main(["--workload", cell, "--seed", "2600000035",
                   "--seconds", "1", "--trace", "1", "--platform", "cpu"],
                  scale={"actors": 2048})
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    metrics = json.loads(out[-1])["metrics"]
    assert metrics["setup_build_s"]["value"] > 0
    assert metrics["setup_cold_launch_s"]["value"] > 0
    if cell.startswith("ring"):
        assert not set(metrics) & {"counter_read_us", "window_dispatch_us"}
    else:
        # a second of this tiny world is more windows than the ring of
        # 64 holds, so window_dispatch_us has nothing to say here
        assert metrics["counter_read_us"]["value"] > 0
    assert not set(metrics) & {"launch_lead_us", "wait_return_us",
                               "hop_launch_lead_us", "hop_wait_return_us"}
