"""Flight recorder + stall watchdog tests (PROFILE.md §11): the
always-on bounded black box, postmortem dumps on stop/crash/SIGQUIT,
the watchdog converting a deliberately wedged run into a structured
postmortem + int-coded PonyStallError, stable error codes, and the
`doctor --postmortem` CLI."""

import json
import os
import time

import pytest

import _child
from ponyc_tpu import I32, Runtime, RuntimeOptions, actor, behaviour
from ponyc_tpu import flight
from ponyc_tpu.errors import ERROR_CODES, PonyError, PonyStallError, \
    error_code
from ponyc_tpu.models import ring

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _opts(**kw):
    base = dict(mailbox_cap=8, batch=1, max_sends=1, msg_words=1,
                spill_cap=64, inject_slots=8)
    base.update(kw)
    return RuntimeOptions(**base)


# ------------------------------------------------------ recorder basics

def test_recorder_always_on_and_bounded(tmp_path):
    """The black box exists on every runtime (no opt-in), records one
    entry per retired window, and its rings stay bounded."""
    path = str(tmp_path / "an.csv")
    rt, ids = ring.build(8, _opts(flight_windows=4, analysis_path=path))
    assert rt._flight is not None           # always-on
    rt.send(int(ids[0]), ring.RingNode.token, 200)
    rt.run()
    fr = rt._flight
    assert 1 <= len(fr.windows) <= 4        # bounded by flight_windows
    w = fr.windows[-1]
    assert set(w) >= {"t_ms", "step", "ticks", "budget", "gap_us",
                      "pipelined", "processed", "delivered", "occ_sum",
                      "occ_max", "qw_p99", "flags"}
    assert w["flags"]["exit"]               # ring exits at hops==1
    assert w["processed"] == 200
    rt.stop()


def test_recorder_gc_events_and_host_mail(tmp_path):
    @actor
    class HostEcho:
        n: I32
        HOST = True

        @behaviour
        def ping(self, st, v: I32):
            return {**st, "n": st["n"] + v}

    rt = Runtime(_opts(analysis_path=str(tmp_path / "an.csv")))
    rt.declare(HostEcho, 2).start()
    h = rt.spawn(HostEcho)
    rt.send(h, HostEcho.ping, 3)
    rt.run()
    rt.release([h])
    rt.gc()
    fr = rt._flight
    kinds = [e["kind"] for e in fr.events]
    assert "gc" in kinds
    assert any(m["behaviour"] == "HostEcho.ping" for m in fr.host_mail)
    rt.stop()


def test_stop_postmortem_dump_roundtrip(tmp_path, capfd):
    """Runtime.stop(postmortem=True) writes a valid structured dump
    (atomic .postmortem.json) and prints the human text; the file
    loads back through the doctor's reader."""
    path = str(tmp_path / "an.csv")
    rt, ids = ring.build(8, _opts(analysis_path=path))
    rt.send(int(ids[0]), ring.RingNode.token, 30)
    rt.run()
    rt.stop(postmortem=True)
    pm_path = path + ".postmortem.json"
    assert rt._flight.last_dump == pm_path
    pm = flight.load_postmortem(pm_path)
    assert pm["version"] == flight.POSTMORTEM_VERSION
    assert pm["reason"].startswith("stop")
    assert pm["steps_run"] == rt.steps_run
    assert pm["windows"] and pm["options"]["mailbox_cap"] == 8
    assert pm["phase"]["name"] == "idle"
    err = capfd.readouterr().err
    assert "flight-recorder postmortem" in err
    line, detail = flight.diagnose_postmortem(pm)
    assert line.startswith("SNAPSHOT")
    assert "windows" in detail


def test_crash_dump_on_fatal_run_error(tmp_path):
    """Any exceptional run() exit dumps the black box with the reason
    and the coded-error evidence."""

    @actor
    class Bad:
        n: I32
        HOST = True

        @behaviour
        def boom(self, st, v: I32):
            raise ValueError("kaboom")

    path = str(tmp_path / "an.csv")
    rt = Runtime(_opts(analysis_path=path))
    rt.declare(Bad, 2).start()
    b = rt.spawn(Bad)
    rt.send(b, Bad.boom, 1)
    with pytest.raises(ValueError, match="kaboom"):
        rt.run()
    pm = flight.load_postmortem(path + ".postmortem.json")
    assert pm["reason"].startswith("crash: ValueError")
    line, _ = flight.diagnose_postmortem(pm)
    assert line.startswith("CRASHED")


# ---------------------------------------------------------- error codes

def test_error_code_table_is_stable():
    """The code table is operational API (metrics labels, postmortems,
    alert rules): pin it."""
    assert ERROR_CODES == {
        "PonyError": 1, "SpillOverflowError": 2,
        "SpawnCapacityError": 3, "BlobCapacityError": 4,
        "CapabilityError": 5, "VerifyError": 6, "PonyStallError": 7,
        # Durable worlds (ISSUE 8) — codes are append-only.
        "SnapshotCorruptError": 8, "SnapshotFormatError": 9,
        "SnapshotGeometryError": 10, "PoisonError": 11,
        # Serving front door (ISSUE 9) — wire reply statuses too.
        "FrameError": 12, "ServeBusyError": 13,
        "ServeDeadlineError": 14}


def test_error_classes_expose_codes():
    from ponyc_tpu.hostmem import CapabilityError
    from ponyc_tpu.runtime.runtime import (BlobCapacityError,
                                           SpawnCapacityError,
                                           SpillOverflowError)
    from ponyc_tpu.verify import VerifyError
    assert SpillOverflowError.code == 2
    assert SpawnCapacityError.code == 3
    assert BlobCapacityError.code == 4
    assert CapabilityError.code == 5
    assert VerifyError.code == 6
    assert PonyStallError.code == 7
    assert error_code(SpillOverflowError("x")) == 2
    assert error_code(PonyError(42)) == 42        # instance code wins
    assert error_code(PonyError()) == 1
    assert error_code(ValueError("x")) == 0       # not a runtime error


def test_fatal_errors_count_for_metrics(tmp_path):
    """A fatal aux flag raise lands in rt._error_counts — the
    pony_tpu_errors_total{class=,code=} label source."""
    from ponyc_tpu.runtime.engine import zero_aux
    rt, _ids = ring.build(8, _opts(analysis_path=str(tmp_path / "a.csv")))
    from ponyc_tpu.runtime.runtime import SpillOverflowError
    a = zero_aux()._replace(spill_overflow=True)
    with pytest.raises(SpillOverflowError):
        rt._fatal_checks(a)
    assert rt._error_counts[("SpillOverflowError", 2)] == 1
    assert any(e["kind"] == "error" for e in rt._flight.events)
    rt.stop()


# ------------------------------------------------------------- watchdog

def _stopped(wd):
    """check() is pure; the live monitor polls the stamp a test fakes
    every 0.25 s, would trip on it too, and its trip() SIGINTs the main
    thread — under xdist that ends the worker and the session (PR 31,
    seen in tests/test_metrics.py). So it is stopped first."""
    wd.close()
    wd.join(5.0)
    assert not wd.is_alive()
    return wd


def test_watchdog_check_pure():
    """Deadline evaluation against synthetic phase stamps: armed phases
    trip past the (scaled) deadline, healthy phases never do."""
    rt, _ids = ring.build(8, _opts(watchdog_s=1.0))
    wd = _stopped(rt._watchdog)
    try:
        now = time.monotonic()
        # warm runtime: flush the cold-phase grace
        rt._rl_windows = 5
        rt._wd_stamp = ("host-work", 7, now - 0.5)
        assert wd.check(now) is None            # within deadline
        rt._wd_stamp = ("host-work", 8, now - 1.5)
        trip = wd.check(now)
        assert trip is not None and trip["phase"] == "host-work"
        assert trip["age_s"] >= 1.5 and trip["deadline_s"] == 1.0
        # quiescent/idle never trip, however old the stamp
        for phase in ("quiescent", "idle"):
            rt._wd_stamp = (phase, 9, now - 1e6)
            assert wd.check(now) is None
        # controller growth scales the deadline (window 4x initial)
        rt._wd_stamp = ("in-flight", 10, now - 1.5)
        rt._controller.window = rt._qi_loaded * 4
        assert wd.check(now) is None            # 4x deadline now
        rt._wd_stamp = ("in-flight", 11, now - 4.5)
        assert wd.check(now) is not None
    finally:
        rt.stop()


def test_watchdog_cold_phase_grace():
    """The first window's trace+compile must not read as a stall: cold
    device phases get COLD_FACTOR x deadline."""
    rt, _ids = ring.build(8, _opts(watchdog_s=1.0))
    wd = _stopped(rt._watchdog)
    try:
        now = time.monotonic()
        assert rt._rl_windows == 0              # nothing retired yet
        rt._wd_stamp = ("dispatching", 1, now - 2.0)
        assert wd.check(now) is None            # < 10s cold deadline
        rt._wd_stamp = ("dispatching", 2, now - 11.0)
        assert wd.check(now) is not None        # even cold has a limit
        # host-work never gets the cold grace (no compile there)
        rt._wd_stamp = ("host-work", 3, now - 2.0)
        assert wd.check(now) is not None
    finally:
        rt.stop()


def test_watchdog_quiet_run_never_trips(tmp_path):
    """A normal run with a tight-ish deadline completes untripped."""
    rt, ids = ring.build(8, _opts(watchdog_s=5.0,
                                  analysis_path=str(tmp_path / "a.csv")))
    rt.send(int(ids[0]), ring.RingNode.token, 50)
    assert rt.run() == 0
    assert rt._watchdog.tripped is None
    rt.stop()
    assert rt._watchdog is None                 # stop() reaps the thread


STALL_SCRIPT = """
import json, sys, time
sys.path.insert(0, {root!r})
from ponyc_tpu.platforms import force_cpu
force_cpu()
from ponyc_tpu import I32, Runtime, RuntimeOptions, actor, behaviour
from ponyc_tpu.errors import PonyStallError

@actor
class Wedge:
    n: I32
    HOST = True

    @behaviour
    def jam(self, st, v: I32):
        time.sleep(600)            # the deliberate stall
        return st

rt = Runtime(RuntimeOptions(
    mailbox_cap=8, batch=1, max_sends=1, msg_words=1,
    watchdog_s=0.6, analysis_path={apath!r}))
rt.declare(Wedge, 2).start()
w = rt.spawn(Wedge)
rt.send(w, Wedge.jam, 1)
t0 = time.monotonic()
try:
    rt.run()
    print("NO-RAISE")
except PonyStallError as e:
    print(json.dumps({{"code": e.code, "phase": e.phase,
                      "postmortem": e.postmortem,
                      "elapsed_s": round(time.monotonic() - t0, 1)}}))
    sys.exit(42)
"""


def test_watchdog_trips_wedged_run_subprocess(tmp_path):
    """ACCEPTANCE: a deliberately wedged run is converted by the
    watchdog into a structured postmortem + int-coded PonyStallError
    within the deadline — instead of the silent forever-hang."""
    apath = str(tmp_path / "stall.csv")
    code = STALL_SCRIPT.format(root=ROOT, apath=apath)
    p = _child.script(code)
    assert p.returncode == 42, (p.returncode, p.stdout, p.stderr)
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["code"] == ERROR_CODES["PonyStallError"] == 7
    assert out["phase"] == "host-work"
    # "within the deadline": the stall lasted 600s, the conversion took
    # seconds (deadline 0.6s + trip poll + signal delivery + unwind).
    assert out["elapsed_s"] < 60
    # The watchdog's postmortem is on disk and structurally valid.
    pm = flight.load_postmortem(out["postmortem"])
    assert pm["reason"].startswith("watchdog")
    assert pm["watchdog"]["tripped"]["phase"] == "host-work"
    assert any(e["kind"] == "watchdog_trip" for e in pm["events"])
    line, _ = flight.diagnose_postmortem(pm)
    assert line.startswith("STALLED")
    assert "host behaviour" in line            # the phase hint
    assert "STALLED" in p.stderr               # loud on the way down


SIGQUIT_SCRIPT = """
import os, signal, sys
sys.path.insert(0, {root!r})
from ponyc_tpu.platforms import force_cpu
force_cpu()
from ponyc_tpu import I32, Runtime, RuntimeOptions, actor, behaviour

@actor
class Poker:
    n: I32
    HOST = True

    @behaviour
    def poke(self, st, v: I32):
        os.kill(os.getpid(), signal.SIGQUIT)   # operator hits ^\\
        self.exit(0, when=v <= 0)
        self.send(self.actor_id, Poker.poke, v - 1, when=v > 0)
        return st

rt = Runtime(RuntimeOptions(
    mailbox_cap=8, batch=1, max_sends=2, msg_words=1,
    analysis_path={apath!r}))
rt.declare(Poker, 2).start()
p = rt.spawn(Poker)
rt.send(p, Poker.poke, 2)
code = rt.run()
print("EXIT", code, "DUMPS", rt._flight.dumps)
sys.exit(code)
"""


def test_sigquit_dumps_and_continues(tmp_path):
    """SIGQUIT mid-run dumps the flight recorder and the run carries on
    to its normal exit (dump-and-continue, unlike SIGTERM)."""
    apath = str(tmp_path / "sq.csv")
    code = SIGQUIT_SCRIPT.format(root=ROOT, apath=apath)
    p = _child.script(code)
    assert p.returncode == 0, (p.returncode, p.stdout, p.stderr)
    assert "EXIT 0" in p.stdout
    assert "DUMPS 3" in p.stdout               # one per SIGQUIT
    pm = flight.load_postmortem(apath + ".postmortem.json")
    assert pm["reason"] == "SIGQUIT"
    assert "flight-recorder postmortem" in p.stderr


# ------------------------------------------- backend-init stall verdict

def test_backend_init_stall_diagnosis():
    """A backend init that never returns is diagnosed from the
    watchdog's own postmortem — in-process evidence only: there is no
    probe child and no probe postmortem any more (a program runs on
    what JAX resolves, and fails if that fails)."""
    assert not hasattr(flight, "probe_postmortem")
    pm = {"version": flight.POSTMORTEM_VERSION,
          "reason": "watchdog: phase 'backend-init' made no progress "
                    "for 40.0s (deadline 30.0s)",
          "phase": {"name": "backend-init", "epoch": 0, "age_s": 40.0},
          "steps_run": 0, "env": {"env": {}, "libtpu_importable": True}}
    json.dumps(pm)                              # must serialise
    line, detail = flight.diagnose_postmortem(pm)
    assert line.startswith("STALLED: watchdog: phase 'backend-init'")
    assert "another process holding the chip" in line
    assert "probe" not in line and "probe" not in detail
    assert "libtpu_importable=True" in detail


# ----------------------------------------------------------- doctor CLI

def test_doctor_cli_postmortem(tmp_path, capsys):
    from ponyc_tpu.__main__ import main as cli_main
    path = str(tmp_path / "an.csv")
    rt, ids = ring.build(8, _opts(analysis_path=path))
    rt.send(int(ids[0]), ring.RingNode.token, 20)
    rt.run()
    rt.stop(postmortem=True)
    capsys.readouterr()
    # A plain snapshot diagnoses healthy (exit 0).
    assert cli_main(["doctor", "--postmortem",
                     path + ".postmortem.json"]) == 0
    out = capsys.readouterr().out
    assert out.startswith("SNAPSHOT")
    assert "flight-recorder postmortem" in out
    # A stall postmortem exits 1.
    stall = rt._flight.postmortem("watchdog: phase 'in-flight' made no "
                                  "progress for 9.0s (deadline 3.0s)")
    spath = str(tmp_path / "stall.json")
    json.dump(stall, open(spath, "w"))
    assert cli_main(["doctor", "--postmortem", spath]) == 1
    assert capsys.readouterr().out.startswith("STALLED")


def test_doctor_cli_refuses_a_json_that_is_no_postmortem(tmp_path, capsys):
    """`doctor --postmortem` reads flight-recorder postmortems only: any
    other json (a benchmark's result line, say) is a usage error, not a
    diagnosis."""
    from ponyc_tpu.__main__ import main as cli_main
    other_json = {"metric": "x", "value": 1,
                  "postmortem": {"reason": "tpu_init_failed"}}
    path = str(tmp_path / "result.json")
    json.dump(other_json, open(path, "w"))
    assert cli_main(["doctor", "--postmortem", path]) == 2
    assert "not a ponyc_tpu postmortem" in capsys.readouterr().err


def test_doctor_cli_usage_errors(tmp_path):
    from ponyc_tpu.__main__ import main as cli_main
    assert cli_main(["doctor"]) == 2                    # no target
    assert cli_main(["doctor", "--postmortem"]) == 2    # missing file
    assert cli_main(["doctor", "--postmortem",
                     str(tmp_path / "absent.json")]) == 2
    bad = str(tmp_path / "bad.json")
    open(bad, "w").write("{}")
    assert cli_main(["doctor", "--postmortem", bad]) == 2


def test_watchdog_option_validation():
    with pytest.raises(ValueError, match="watchdog_s"):
        RuntimeOptions(watchdog_s=0.0)
    with pytest.raises(ValueError, match="flight_windows"):
        RuntimeOptions(flight_windows=0)


# ------------------------------------ the window's clock (ISSUE 24)

def test_window_records_tile_the_wall_clock(tmp_path):
    """Every window record says where its wall clock went: wall_ms >=
    wait_ms >= 0, and since_prev_ms + wall_ms of consecutive records
    tile the time from the first retire to the last — across two run()
    calls too (since_prev_ms is not reset at run() entry)."""
    rt, ids = ring.build(8, _opts(flight_windows=256, tuning_cache="off",
                                  quiesce_interval=16,
                                  analysis_path=str(tmp_path / "an.csv")))
    rt.send(int(ids[0]), ring.RingNode.token, 10_000)
    rt.run(max_steps=200)
    t_mid = time.perf_counter()
    time.sleep(0.05)                       # between two run() calls
    rt.run(max_steps=200)
    t_end = time.perf_counter()
    recs = list(rt._flight.windows)
    rt.stop()
    assert len(recs) >= 8
    for w in recs:
        assert w["wall_ms"] >= w["wait_ms"] >= 0
        assert w["since_prev_ms"] >= 0
        assert not (w["pipelined"] and w["since_prev_ms"])
    tiled = sum(w["since_prev_ms"] + w["wall_ms"] for w in recs[1:])
    # the ring's own clock (t_ms is stamped at the retire's accounting)
    assert tiled == pytest.approx(recs[-1]["t_ms"] - recs[0]["t_ms"],
                                  rel=0.05, abs=2.0)
    # the sleep between the calls has an owner
    second = [w for w in recs if w["since_prev_ms"] >= 50.0]
    assert len(second) == 1
    assert t_end - t_mid >= second[0]["since_prev_ms"] / 1e3
    rl = rt.run_loop_stats()
    assert rl["windows_wall_s"] == pytest.approx(
        sum(w["wall_ms"] for w in recs) / 1e3, rel=1e-3)
    assert set(rl["phase_s"]) >= {"dispatching", "wait", "host-work"}


# a perf_counter difference and its rounding to 1e-4 ms, generously
GRAIN_MS = 0.01


def _two_runs_with_reads_between(tmp_path):
    """A ring run twice; between the calls the caller polls a counter
    and reads a cohort, as a benchmark's segment loop does."""
    rt, ids = ring.build(8, _opts(flight_windows=256, tuning_cache="off",
                                  quiesce_interval=16,
                                  analysis_path=str(tmp_path / "an.csv")))
    rt.send(int(ids[0]), ring.RingNode.token, 10_000)
    rt.run(max_steps=120)
    first = len(rt._flight.windows)
    assert rt.counter("n_processed") > 0
    rt.cohort_state(ring.RingNode)
    rt.run(max_steps=120)
    return rt, list(rt._flight.windows), first


def test_window_records_itemise_the_dispatch(tmp_path):
    """dispatch_ms is the window's `pony:dispatching`: the first stretch
    of a sync-point window's wall_ms (a pipelined window's launch ran
    inside the record before it), and the windows' sum is the phase's
    seconds."""
    rt, recs, _first = _two_runs_with_reads_between(tmp_path)
    rl = rt.run_loop_stats()
    rt.stop()
    assert len(recs) >= 8 and any(w["pipelined"] for w in recs)
    for w in recs:
        assert w["dispatch_ms"] > 0
        if not w["pipelined"]:
            assert w["dispatch_ms"] <= w["wall_ms"] + GRAIN_MS
    # the first launch compiled: it is the cold one, to the clock's grain
    assert recs[0]["dispatch_ms"] == pytest.approx(
        rl["cold_dispatch_s"] * 1e3, abs=GRAIN_MS)
    assert recs[0]["dispatch_ms"] > max(w["dispatch_ms"] for w in recs[1:])
    # dispatch_ms starts a few lines before the span does
    assert sum(w["dispatch_ms"] for w in recs) == pytest.approx(
        rl["phase_s"]["dispatching"] * 1e3, rel=0.05, abs=1.0)


def test_window_records_itemise_since_prev(tmp_path):
    """outside_ms is the itemisation of since_prev_ms: its sum lies
    inside it, a pipelined window has none, the first record has no
    record before it (set-up's calls are in phase_s alone), and a
    counter() and a cohort_state() between two run() calls land in the
    next record."""
    rt, recs, first = _two_runs_with_reads_between(tmp_path)
    rl = rt.run_loop_stats()
    rt.stop()
    for w in recs:
        assert sum(w["outside_ms"].values()) <= w["since_prev_ms"] + GRAIN_MS
        assert not (w["pipelined"] and w["outside_ms"])
    assert recs[0]["outside_ms"] == {} and rl["phase_s"]["start"] > 0
    second = recs[first]
    assert set(second["outside_ms"]) == {"counter", "read"}
    assert all(ms > 0 for ms in second["outside_ms"].values())
    assert all(w["outside_ms"] == {} for i, w in enumerate(recs)
               if i != first)
    # what the records hold is what the phases took, in ms
    assert second["outside_ms"]["counter"] == pytest.approx(
        rl["phase_s"]["counter"] * 1e3, abs=GRAIN_MS)
    assert second["outside_ms"]["read"] == pytest.approx(
        rl["phase_s"]["read"] * 1e3, abs=GRAIN_MS)


def test_postmortem_renders_the_dispatch_and_the_outside(tmp_path):
    rt, _recs, _first = _two_runs_with_reads_between(tmp_path)
    rt.stop(postmortem=True)
    pm = flight.load_postmortem(str(tmp_path / "an.csv") + ".postmortem.json")
    text = flight.render_postmortem(pm)
    assert "dispatch=" in text
    assert any(w["outside_ms"] for w in pm["windows"])
    pm["windows"] = [w for w in pm["windows"] if w["outside_ms"]]
    assert "outside=counter:" in flight.render_postmortem(pm)


def test_old_postmortem_without_the_clock_still_renders(tmp_path, capsys):
    """A postmortem written before the window records had wall_ms /
    wait_ms / since_prev_ms renders as it did; a new one shows them."""
    from ponyc_tpu.__main__ import main as cli_main
    path = str(tmp_path / "an.csv")
    rt, ids = ring.build(8, _opts(analysis_path=path))
    rt.send(int(ids[0]), ring.RingNode.token, 20)
    rt.run()
    rt.stop(postmortem=True)
    capsys.readouterr()
    pm = flight.load_postmortem(path + ".postmortem.json")
    assert "wall=" in flight.render_postmortem(pm)
    for w in pm["windows"]:
        for key in ("wall_ms", "wait_ms", "since_prev_ms", "dispatch_ms",
                    "outside_ms"):
            del w[key]
    old = str(tmp_path / "old.json")
    json.dump(pm, open(old, "w"))
    assert cli_main(["doctor", "--postmortem", old]) == 0
    out = capsys.readouterr().out
    assert "last " in out and "wall=" not in out and "gap=" in out


def test_latest_follows_the_newest_runtime_and_keeps_none_alive():
    import gc
    import weakref
    first, _ = ring.build(8, _opts())
    assert flight.latest() is first._flight
    second, _ = ring.build(8, _opts())
    assert flight.latest() is second._flight
    first.stop()
    second.stop()
    gone = weakref.ref(second)
    del second
    gc.collect()
    assert gone() is None               # latest() held no strong reference
    assert flight.latest() is None      # ... and does not fall back
    del first


def test_metrics_expose_the_phase_seconds(tmp_path):
    from ponyc_tpu import metrics
    rt, ids = ring.build(8, _opts(analysis_path=str(tmp_path / "an.csv")))
    rt.send(int(ids[0]), ring.RingNode.token, 20)
    rt.run()
    text = metrics.prometheus_text(metrics.snapshot(rt))
    rt.stop()
    assert 'pony_tpu_run_phase_seconds_total{phase="wait"}' in text
    assert 'pony_tpu_run_phase_seconds_total{phase="spawn"}' in text
    assert 'pony_tpu_run_phase_calls_total{phase="start"} 1' in text
    assert "pony_tpu_cold_dispatch_seconds_total" in text
    assert "pony_tpu_windows_wall_seconds_total" in text
