"""The symbol-table readers: `symbol_trace.reduce` on the trace
recorded on the v5e with a hand-made table, the join's rules on
hand-made data, and the four new readers where there is nothing to
read and end to end on the CPU backend."""

import importlib
import json

import pytest

from benchmarks import phase_trace, run, symbol_trace
from benchmarks.tests.test_phase_trace import recorded

NEW = ("unnamed_pct", "hop_unnamed_pct", "dispatch_named_ms",
       "plain_gather_ms")
# an operation of the recording that the profiler left without a scope
# (the drain's zeros: the fusion's root is a bitcast), and one the
# hand-made table leaves out
LOST = "broadcast_bitcast_fusion"
NO_ROW = "fusion.20"


def reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}").read


def row(short: str, scope, how="own", **more) -> dict:
    """A table row for the event whose short name is `short`."""
    name, shape = short.split(" ", 1)
    shape = "(s32[]{:T(128)}, s32[]{:T(128)})" if shape == "(tuple)" \
        else shape + "{0:T(1024)}"
    return {"name": name, "opcode": "fusion", "shape": shape,
            "kind": "other", "scope": scope, "how": how if scope else "none",
            "s1": None, "table_s1": None, "table_bytes": None,
            "index_count": None, **more}


def hand_made_table(data: dict) -> dict:
    """Every operation of the recording named as the profiler named it,
    but: LOST from inside its fusion, NO_ROW not in the table."""
    table = {}
    for name, _start, _dur, op_name in data["device"][0]:
        if name.split(" ")[0] == NO_ROW:
            continue
        table[name] = row(name, phase_trace.scope_of(op_name))
        if name.split(" ")[0] == LOST:
            table[name] = row(name, "drain", how="inside")
    return {"window": list(table.values())}


def test_recorded_trace_by_a_hand_made_table():
    data = recorded()
    phases = phase_trace.reduce(data, data["ticks"])
    assert any(n.split(" ")[0] == LOST and op is None
               for n, _s, _d, op in data["device"][0])
    got = symbol_trace.reduce(data, hand_made_table(data), data["ticks"])
    assert got["busy_s"] == pytest.approx(phases["busy_s"], rel=1e-12)
    assert sum(r["s"] for r in got["scopes"].values()) == \
        pytest.approx(phases["busy_s"], rel=1e-9)
    lost = sum(s for n, s in phase_trace.reduce(
        {**data, "device": [[e for e in data["device"][0]
                             if e[0].split(" ")[0] == LOST]]}, 3
    )["phases"]["unscoped"]["top"])
    assert lost > 0
    # the unnamed fusion's time moved to its scope ...
    assert got["scopes"]["drain"]["s"] == pytest.approx(
        phases["phases"]["drain"]["s"] + lost, rel=1e-9)
    assert got["scopes"]["drain"]["how"]["inside"] == \
        pytest.approx(lost, rel=1e-9)
    # ... and the event no row matches stays, under `unnamed`
    rebuilt = phases["phases"]["delivery/rebuild"]
    gone = dict(rebuilt["top"])[next(n for n, _ in rebuilt["top"]
                                     if n.startswith(NO_ROW + " "))]
    unnamed = got["scopes"][symbol_trace.UNNAMED]
    assert unnamed["how"][symbol_trace.NO_ROW] == pytest.approx(gone)
    assert unnamed["s"] == pytest.approx(
        phases["phases"]["unscoped"]["s"] - lost + gone, rel=1e-9)
    assert "symbols over 3 traced ticks" in symbol_trace.table(
        got, phases["busy_s"])


def test_the_join_is_by_name_and_shape_and_by_the_collectors_span():
    """One name in two programs: told apart by shape; with the same
    shape, the collector's inside a `pony:gc` span, the window's
    outside. A gather without `S(1)` on a long list is plain memory's."""
    long = symbol_trace.LONG
    symbols = {
        "window": [row("fusion.1 s32[8]", "drain"),
                   row("fusion.2 s32[8]", "delivery/permute", kind="gather",
                       s1=False, table_bytes=64, index_count=long),
                   row("fusion.3 s32[8]", "delivery/rebuild", kind="gather",
                       s1=True, table_bytes=64, index_count=long),
                   row("fusion.4 s32[8]", "unmute", kind="gather",
                       s1=False, table_bytes=64, index_count=long - 1),
                   # the output has the mark, the table has not: plain
                   row("fusion.5 s32[8]", "route", kind="gather", s1=True,
                       table_s1=False, table_bytes=64, index_count=long)],
        "gc": [row("fusion.1 s32[8]", "gc_mark/hop"),
               row("fusion.2 s32[16]", "gc_mark/roots")]}
    data = {"host": [["segment", 0.0, 10000.0, None, None],
                     ["pony:gc", 5000.0, 2000.0, 7, None],
                     ["pony:gc", 7000.0, 0.0, None, None]],
            "device": [[["%fusion.1 = s32[8]{0} fusion(%p)", 100.0, 10.0, None],
                        ["fusion.1 s32[8]", 5100.0, 30.0, None],
                        ["fusion.2 s32[8]", 200.0, 100.0, None],
                        ["fusion.2 s32[16]", 5200.0, 40.0, None],
                        ["fusion.3 s32[8]", 400.0, 50.0, None],
                        ["fusion.4 s32[8]", 500.0, 60.0, None],
                        ["fusion.5 s32[8]", 700.0, 7.0, None],
                        ["fusion.2 s32[32]", 600.0, 5.0, None]]]}
    got = symbol_trace.reduce(data, symbols, 2)
    ns = {scope: round(1e9 * rec["s"]) for scope, rec in got["scopes"].items()}
    assert ns == {"drain": 10, "gc_mark/hop": 30, "delivery/permute": 100,
                  "gc_mark/roots": 40, "delivery/rebuild": 50, "unmute": 60,
                  "route": 7, symbol_trace.UNNAMED: 5}
    assert 1e9 * symbol_trace.plain(got) == pytest.approx(107.0)
    assert {op["name"]: symbol_trace.mark(op) for op in got["indexed"]} == {
        "fusion.2 s32[8]": "plain", "fusion.3 s32[8]": "S(1)",
        "fusion.4 s32[8]": "plain", "fusion.5 s32[8]": "out"}
    assert 1e9 * symbol_trace.under(got, "drain", "dispatch") == \
        pytest.approx(10.0)
    assert [op["name"] for op in got["indexed"]] == [
        "fusion.2 s32[8]", "fusion.4 s32[8]", "fusion.3 s32[8]",
        "fusion.5 s32[8]"]


@pytest.mark.parametrize("case", ["no trace", "no table"])
def test_new_readers_say_nothing_without_a_trace_or_a_table(
        case, monkeypatch, tmp_path):
    """The parent's program makes no table; a run without `--trace 1`
    writes no trace. Nothing to read, nothing raised."""
    ctx = {"trace": {"ticks": 3}, "window": {}}
    if case == "no trace":
        monkeypatch.setattr(phase_trace, "TRACE_DIR", str(tmp_path))
    else:
        trace = tmp_path / "plugins" / "profile" / "t"
        trace.mkdir(parents=True)
        (trace / "x.xplane.pb").write_bytes(b"")
        monkeypatch.setattr(phase_trace, "TRACE_DIR", str(tmp_path))
        monkeypatch.setattr(phase_trace, "of_run", lambda _ctx: None)
        monkeypatch.setattr(symbol_trace, "recorder",
                            lambda: type("R", (), {"rt": object()})())
    symbol_trace._cache.clear()
    for name in NEW:
        assert reader(name)(ctx) is None, name
    symbol_trace._cache.clear()


def test_new_readers_end_to_end_on_the_cpu(capsys):
    """The CPU backend's trace has no device plane: the table is made
    (the runtime is reached, the window is not compiled again) and the
    four readers say nothing."""
    rc = run.main(["--workload", "ubench-1m.random", "--seed", "6",
                   "--seconds", "1", "--trace", "1", "--platform", "cpu"],
                  scale={"actors": 2048})
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    assert result["correct"] and not set(result["metrics"]) & set(NEW)
    symbols = symbol_trace.symbols_of_run()
    assert symbols and {r["scope"] for r in symbols["window"]} >= \
        {"drain", "delivery/rebuild", "delivery/plan"}
