"""The Task Bench cell end to end on the CPU backend at a tiny width,
with both `--trace` values, its nine layer metrics on recorded
contexts, and the payload's least bytes. (The reference by hand and the
world tick by tick are tier-1's: `tests/test_taskbench_payload.py`.)"""

import importlib
import json

import pytest

from benchmarks import payload_bytes, phase_trace, run
from benchmarks.tests.conftest import ROOT

CELL = "taskbench-stencil.payload"
SCALE = {"actors": 64}              # the width; the payload is never cut
TRACED = ("payload_read_ms", "payload_write_ms", "pool_alloc_ms",
          "pool_free_ms", "pool_reserve_ms", "payload_word_ns",
          "payload_roofline")
COUNTED = ("pool_live_pct", "allocs_per_tick")
PEAK = {"hbm_bytes_per_s": 819e9}


def reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}").read


def last_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def files():
    with open(f"{ROOT}/benchmarks/configs/taskbench-stencil.json") as f:
        cfg = json.load(f)
    with open(f"{ROOT}/benchmarks/traffic/payload.json") as f:
        return cfg, json.load(f)


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_is_correct(trace, capsys):
    with open(f"{ROOT}/BENCHMARK.json") as f:
        bench = json.load(f)
    rc = run.main(["--workload", CELL, "--seed", str(2**31 + 53),
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
    # the CPU's trace holds no device plane: the seven device shares are
    # left out and nothing raises; the two counters are the books'
    assert not set(TRACED) & set(got)
    assert got["pool_live_pct"]["value"] == pytest.approx(100 * 3 / 7)
    assert got["allocs_per_tick"]["value"] == 3 * SCALE["actors"]
    assert {"tick_ms", "setup_build_s", "host_gap_pct"} <= set(got)


def test_the_cell_is_not_correct_when_a_payload_is_tampered(monkeypatch,
                                                            capsys):
    """`correct` reads the payloads: a world whose host-stored timestep-0
    buffers carry one wrong pair each is refused on its first ticks."""
    from benchmarks.worlds import taskbench
    from ponyc_tpu import Runtime
    real = Runtime.blob_store_many

    def torn(self, count, words, **kw):
        words = words.copy()
        words[:, 5] += 1
        return real(self, count, words, **kw)
    monkeypatch.setattr(taskbench.Runtime, "blob_store_many", torn)
    rc = run.main(["--workload", CELL, "--seed", "7", "--seconds", "0.2",
                   "--trace", "0", "--platform", "cpu"], scale=SCALE)
    assert rc == 0
    result = last_line(capsys)
    assert result["correct"] is False
    assert result["failed"] >= SCALE["actors"]


def test_the_payloads_least_bytes():
    cfg, traffic = files()
    assert payload_bytes.payload_words(traffic) == 32
    assert payload_bytes.payloads_per_tick(cfg, traffic) == 196_608
    assert payload_bytes.words_moved_per_tick(cfg, traffic) \
        == 2 * 32 * 196_608
    # 32 words written and read, the handle's generation, the slot's flag
    assert payload_bytes.payload_bytes(traffic) == 2 * 128 + 4 + 1
    assert payload_bytes.tick_bytes(cfg, traffic) == 196_608 * 261
    assert payload_bytes.tick_min_seconds(cfg, traffic, PEAK) \
        == pytest.approx(196_608 * 261 / 819e9)


def test_readers_on_a_trace_with_and_without_the_scopes(monkeypatch):
    """The five readers each read one scope below `dispatch/heap`;
    `payload_word_ns` is get + set over the words moved; the roofline
    share is the least time over everything at and below
    `dispatch/heap`. A program without the five scopes (the parent)
    leaves all but the roofline out; no trace at all leaves all out."""
    cfg, traffic = files()

    def device(sub):
        under = "jit(multi)/while/body/pony/dispatch/cohort/Point/cond/" \
            "branch_1_fun/while/body/pony/dispatch/heap"
        ns = {"get": 2e6, "set": 6e6, "alloc": 10e6, "free": 1e6}
        events, at = [], 0.0
        for i, (scope, dur) in enumerate(ns.items()):
            events.append([f"fusion.{i}", at, dur,
                           f"{under}{'/' + scope if sub else ''}/op"])
            at += dur
        events.append(["fusion.8", at, 5e5,
                       "jit(multi)/while/body/pony/dispatch/heap"
                       + ("/reserve" if sub else "") + "/sort"])
        events.append(["fusion.9", at + 5e5, 5e5,
                       "jit(multi)/while/body/pony/delivery/gather"])
        return [events]
    host = [["segment", 0.0, 21e6, None, None]]
    ctx = {"trace": {"ticks": 2}, "window": {"ticks": 10}, "cfg": cfg,
           "traffic": traffic, "peak": PEAK}
    named = phase_trace.reduce({"device": device(True), "host": host}, 2)
    monkeypatch.setattr(phase_trace, "of_run", lambda _ctx: named)
    want = {"payload_read_ms": 1.0, "payload_write_ms": 3.0,
            "pool_alloc_ms": 5.0, "pool_free_ms": 0.5,
            "pool_reserve_ms": 0.25}
    for name, ms in want.items():
        assert reader(name)(ctx) == pytest.approx(ms), name
    assert reader("heap_update_ms")(ctx) == pytest.approx(9.75)
    words = 2 * 32 * 196_608
    assert reader("payload_word_ns")(ctx) == pytest.approx(4e6 / words)
    least_ms = 1e3 * 196_608 * 261 / 819e9
    assert reader("payload_roofline")(ctx) \
        == pytest.approx(100 * least_ms / 9.75)
    assert 0 < reader("payload_roofline")(ctx) < 100
    parents = phase_trace.reduce({"device": device(False), "host": host}, 2)
    monkeypatch.setattr(phase_trace, "of_run", lambda _ctx: parents)
    for name in (*want, "payload_word_ns"):
        assert reader(name)(ctx) is None, name
    assert reader("payload_roofline")(ctx) \
        == pytest.approx(100 * least_ms / 9.75)
    monkeypatch.setattr(phase_trace, "of_run", lambda _ctx: None)
    for name in TRACED:
        assert reader(name)(ctx) is None, name


def test_the_counters_read_the_windows_books():
    cfg, _ = files()
    pool = {"allocs": 1_966_080, "frees": 1_966_080,
            "blobs_in_use": 196_608, "slots": cfg["sizes"]["blob_slots"]}
    ctx = {"window": {"ticks": 10, "pool": pool}, "cfg": cfg}
    assert reader("pool_live_pct")(ctx) == pytest.approx(300 / 7)
    assert reader("allocs_per_tick")(ctx) == 196_608
    # a program whose run loop keeps no books (the parent's)
    for name in COUNTED:
        assert reader(name)({"window": {"ticks": 10}, "cfg": cfg}) is None


def test_every_new_metric_is_declared_for_the_cell():
    with open(f"{ROOT}/BENCHMARK.json") as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in TRACED + COUNTED:
        assert declared[name]["moves"] == "msgs_per_s"
        assert declared[name]["workloads"] == [CELL]
        assert callable(reader(name))
        assert declared[name]["layer"] == (
            "window / tick" if name in COUNTED else "formulations / kernels")
        assert declared[name]["source"] == (
            "program_counter" if name in COUNTED else "device_trace")
    assert declared["payload_roofline"]["unit"] == "%"
    # every per-layer metric the one-chip throughput cells report, the
    # heap's own, and the whole tick's share of its roofline
    for m in bench["per_layer"]:
        cells = m.get("workloads", [])
        if "ubench-1m.cycle" in cells and "ubench-1m.sparse" in cells:
            assert CELL in cells, m["name"]
    assert declared["heap_update_ms"]["workloads"] \
        == ["gups-hpcc.stream", CELL]
    assert CELL not in declared["heap_roofline"]["workloads"]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["msgs_per_s"]["workloads"]
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["chips"], cell["traffic"]) \
        == (CELL, 1, "payload")
    config = bench["configs"][-1]
    assert (config["name"], sorted(config["reduced"])) \
        == ("taskbench-stencil", ["steps", "word_bits"])
