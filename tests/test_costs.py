"""Device-cost observatory + perf scoreboard (costs.py, ISSUE 19).

Four layers under test, matching the tentpole:
1. measured capture — XLA's cost/memory analysis of the runtime's REAL
   compiled executables (capture / Runtime.measured_costs /
   opts.cost_capture), memoized, never advancing the world;
2. modelled vs measured — on CPU the record-move probe's bytes/msg must
   agree with costs.modelled_bytes_per_msg's unpacked bytes within
   the divergence tolerance, and a seeded mismatch must trip the loud
   model_divergence flag;
3. the scoreboard — BENCH_HISTORY.jsonl + BENCH_r*.json ingestion,
   like-for-like grouping, the --check regression gate (an injected
   regression fails, the repo's real trajectory passes);
4. the operational surfaces — /metrics gauges, the flight-recorder
   postmortem's measured section (gracefully absent on pre-PR-19
   dumps), and `ponyc_tpu perf` / `doctor --postmortem` exit codes.
"""

import json
import os

import pytest

from ponyc_tpu import RuntimeOptions, costs
from ponyc_tpu.models import ring


def _opts(**kw):
    base = dict(mailbox_cap=8, batch=1, max_sends=1, msg_words=1,
                spill_cap=64, inject_slots=8)
    base.update(kw)
    return RuntimeOptions(**base)


def _ring(**kw):
    rt, ids = ring.build(8, _opts(**kw))
    return rt, ids


@pytest.fixture(scope="module")
def plain_rt():
    """One started ring world shared by the capture-path tests below —
    each test stays independently runnable (capture compiles on demand)
    but a full-file run pays the build + AOT compiles once."""
    rt, ids = _ring()
    yield rt, ids
    rt.stop()


@pytest.fixture(scope="module")
def cc_rt():
    """One cost_capture=True world shared by the eager-capture /
    postmortem / doctor surface tests."""
    rt, ids = _ring(cost_capture=True)
    yield rt, ids
    rt.stop()


# ------------------------------------------------------ measured capture

def test_capture_reads_real_executables_and_memoizes(plain_rt):
    rt, _ = plain_rt
    steps0 = rt.steps_run
    cap = costs.capture(rt)
    # AOT lowering must not advance the world.
    assert rt.steps_run == steps0
    assert cap["version"] == costs.COST_VERSION
    assert set(cap["executables"]) == {"step", "window"}
    for rec in cap["executables"].values():
        assert "error" not in rec
        # CPU reports both analyses on jaxlib 0.4.x; every field is
        # at worst None, never missing.
        assert {"flops", "bytes_accessed", "peak_bytes"} <= set(rec)
        assert rec["bytes_accessed"] and rec["bytes_accessed"] > 0
    # Memoized: same object back, and measured_costs() is the accessor.
    assert costs.capture(rt) is cap
    assert rt.measured_costs() is cap
    assert rt.measured_costs(force=True) is not cap


def test_cost_capture_option_runs_at_start(cc_rt):
    rt, _ = cc_rt
    assert rt._costs is not None
    # start()'s eager capture goes all the way to the judged block.
    assert "model_divergence" in rt._costs


def test_capture_requires_started_runtime():
    from ponyc_tpu import Runtime
    rt = Runtime(_opts())
    rt.declare(ring.RingNode, 8)
    with pytest.raises(RuntimeError, match="start"):
        costs.capture(rt)


# -------------------------------------------------- modelled vs measured

def test_record_probe_agrees_with_model_on_cpu():
    """Acceptance: the measured bytes/msg of the canonical record move
    lands on the model's unpacked bytes within tolerance on CPU."""
    opts = _opts()
    probe = costs.record_move_probe(opts)
    from ponyc_tpu.runtime.state import record_words
    assert probe["record_words"] == record_words(opts)
    modelled = costs.modelled_bytes_per_msg(opts)["unpacked_bytes"]
    assert modelled == 4.0 * record_words(opts)
    assert probe["bytes_per_msg"] is not None
    assert (abs(probe["bytes_per_msg"] - modelled) / modelled
            <= costs.DIVERGENCE_TOLERANCE)


def test_measured_block_clean_world_does_not_diverge(plain_rt, capsys):
    rt, _ = plain_rt
    blk = costs.measured_block(rt)
    div = blk["model_divergence"]
    assert div["diverged"] is False
    assert div["ratio"] == pytest.approx(1.0, rel=0.5)
    assert blk["modelled"]["unpacked_bytes"] > 0
    assert "MODEL DIVERGENCE" not in capsys.readouterr().err
    # the judged block replaces the bare capture memo
    assert rt._costs is blk


def test_seeded_divergence_trips_the_flag(plain_rt, capsys):
    """A model that prices the record at 10x reality must be called
    out — loudly (stderr) and in the block itself."""
    rt, _ = plain_rt
    fake = {"record_words": 2, "unpacked_bytes": 80.0}
    blk = costs.measured_block(rt, modelled=fake)
    assert blk["model_divergence"]["diverged"] is True
    assert "MODEL DIVERGENCE" in capsys.readouterr().err


def test_divergence_verdict_edges():
    assert costs.divergence(8.0, 8.1)["diverged"] is False
    assert costs.divergence(8.0, 20.0)["diverged"] is True
    # absence of evidence is not divergence
    none = costs.divergence(8.0, None)
    assert none["diverged"] is False and none["ratio"] is None
    assert costs.divergence(0.0, 8.0)["diverged"] is False


# -------------------------------------------------- operational surfaces

def test_metrics_exports_phases_and_measured_gauges():
    from ponyc_tpu import metrics
    rt, ids = _ring(analysis=1, cost_capture=True)
    rt.send(int(ids[0]), ring.RingNode.token, 20)
    rt.run()
    snap = metrics.snapshot(rt)
    assert snap["phases"]["dispatch"] == 20
    text = metrics.prometheus_text(snap)
    parsed = metrics.parse_prometheus(text)
    assert parsed[("pony_tpu_phase_work_total",
                   (("phase", "delivery"),))] == 20
    assert parsed[("pony_tpu_measured_bytes_accessed",
                   (("executable", "step"),))] > 0
    assert parsed[("pony_tpu_model_divergence", ())] == 0
    rt.stop()


def test_postmortem_carries_and_renders_measured(cc_rt):
    from ponyc_tpu.flight import render_postmortem
    rt, _ = cc_rt
    pm = rt._flight.postmortem("manual")
    assert pm["measured"] is rt._costs
    text = render_postmortem(pm)
    assert "measured [step]" in text
    assert "model vs measured" in text
    # Pre-PR-19 postmortems have no "measured" key: render degrades.
    del pm["measured"]
    assert "measured [" not in render_postmortem(pm)


def test_doctor_renders_measured_from_postmortem_file(cc_rt, tmp_path,
                                                      capsys):
    from ponyc_tpu.__main__ import cmd_doctor
    rt, _ = cc_rt
    path = str(tmp_path / "w.postmortem.json")
    rt._flight.dump("manual", path=path, out=open(os.devnull, "w"))
    assert cmd_doctor(["--postmortem", path]) == 0
    assert "measured [step]" in capsys.readouterr().out


# --------------------------------------------------------- the scoreboard

def _hist_row(value, **kw):
    row = {"metric": "ubench_actor_messages_per_sec",
           "unit": "msgs/sec/chip", "value": value,
           "vs_baseline": round(value / 3.0e8, 3), "platform": "cpu",
           "delivery": "plan", "actors": 256}
    row.update(kw)
    return row


def _write_history(path, rows):
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


def test_perf_check_detects_injected_regression(tmp_path):
    hist = tmp_path / "BENCH_HISTORY.jsonl"
    _write_history(hist, [_hist_row(1.0e6), _hist_row(1.1e6),
                          _hist_row(4.0e5)])
    rows = costs.load_history(str(tmp_path))
    assert len(rows) == 3
    verdict = costs.perf_check(rows)
    assert not verdict["ok"]
    assert verdict["regressions"][0]["latest"] == 4.0e5
    text = costs.render_perf(rows, verdict)
    assert "REGRESSION" in text and "check: FAIL" in text


def test_perf_check_groups_like_with_like(tmp_path):
    """An explicit CPU run after a TPU run is NOT a regression — and
    neither is a small smoke run after a 1M-actor headline."""
    hist = tmp_path / "BENCH_HISTORY.jsonl"
    _write_history(hist, [
        _hist_row(1.7e7, platform="tpu", actors=1 << 20),
        _hist_row(4.0e6, platform="cpu", actors=131072,
                  unit="msgs/sec/cpu-backend"),
        _hist_row(9.0e5, platform="cpu", actors=256),
    ])
    verdict = costs.perf_check(costs.load_history(str(tmp_path)))
    assert verdict["ok"], verdict["regressions"]


def test_perf_check_flags_model_divergence(tmp_path):
    hist = tmp_path / "BENCH_HISTORY.jsonl"
    _write_history(hist, [_hist_row(1.0e6, model_divergence=True,
                                    divergence_ratio=3.2)])
    verdict = costs.perf_check(costs.load_history(str(tmp_path)))
    assert not verdict["ok"] and verdict["divergent"]


def test_load_history_reads_bench_round_wrappers(tmp_path):
    """BENCH_r*.json is the driver wrapper {n, cmd, rc, tail, parsed}
    — rows come from `parsed`; a failed round (parsed null) skips."""
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"n": 1, "cmd": "x", "rc": 1, "tail": "boom", "parsed": None}))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(
        {"n": 2, "cmd": "x", "rc": 0, "parsed": {
            "metric": "ubench_actor_messages_per_sec",
            "value": 1.7e7, "unit": "msgs/sec/chip",
            "vs_baseline": 0.058,
            "detail": {"platform": "tpu", "actors": 1 << 20,
                       "delivery": "plan"},
            "measured": {"executables": {"step": {
                "bytes_accessed": 123.0}},
                "model_divergence": {"ratio": 1.0,
                                     "diverged": False}}}}))
    rows = costs.load_history(str(tmp_path))
    assert len(rows) == 1
    assert rows[0]["source"] == "BENCH_r02.json"
    assert rows[0]["platform"] == "tpu"
    assert rows[0]["measured_step_bytes"] == 123.0


def _write_trajectory(root):
    """A trajectory shaped like a repo's real one, built where the test
    can see it: one TPU group (a driver-wrapped round record, alone in
    its group) plus one CPU group whose history only rises."""
    (root / "BENCH_r02.json").write_text(json.dumps(
        {"n": 2, "cmd": "x", "rc": 0, "parsed": {
            "metric": "ubench_actor_messages_per_sec",
            "value": 1.7e7, "unit": "msgs/sec/chip",
            "vs_baseline": 0.058,
            "detail": {"platform": "tpu", "actors": 1 << 20,
                       "delivery": "plan"}}}))
    _write_history(root / "BENCH_HISTORY.jsonl", [
        _hist_row(v, actors=131072, unit="msgs/sec/cpu-backend")
        for v in (4.15e6, 4.49e6, 4.53e6)])


def test_perf_check_passes_a_real_shaped_trajectory(tmp_path):
    """Acceptance: round records plus a BENCH_HISTORY.jsonl trail pass
    the gate — the TPU round and the CPU runs are different groups, and
    the CPU trajectory is monotone. (The repo itself commits no bench
    records: costs.load_history's glob simply finds none there.)"""
    _write_trajectory(tmp_path)
    rows = costs.load_history(str(tmp_path))
    assert len(rows) == 4
    assert {r["platform"] for r in rows} == {"tpu", "cpu"}
    verdict = costs.perf_check(rows)
    assert verdict["ok"], verdict["regressions"]
    assert len(verdict["groups"]) == 2


def test_perf_cli_exit_codes(tmp_path, capsys):
    from ponyc_tpu.__main__ import cmd_perf
    # no history at all → 2
    empty = tmp_path / "empty"
    empty.mkdir()
    assert cmd_perf(["--root", str(empty)]) == 2
    # injected regression → 1 with --check, 0 without
    _write_history(tmp_path / "BENCH_HISTORY.jsonl",
                   [_hist_row(1.0e6), _hist_row(4.0e5)])
    assert cmd_perf(["--root", str(tmp_path)]) == 0
    assert cmd_perf(["--root", str(tmp_path), "--check"]) == 1
    # a loose tolerance waves the same history through
    assert cmd_perf(["--root", str(tmp_path), "--check",
                     "--tolerance", "0.9"]) == 0
    # a real-shaped trajectory (TPU group + CPU group) passes the gate
    real = tmp_path / "real"
    real.mkdir()
    _write_trajectory(real)
    assert cmd_perf(["--root", str(real), "--check"]) == 0
    out = capsys.readouterr().out
    assert "scoreboard" in out and "north star" in out
    # usage errors → 2
    assert cmd_perf(["--frobnicate"]) == 2
    assert cmd_perf(["--tolerance"]) == 2
