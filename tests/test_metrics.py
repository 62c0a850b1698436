"""Metrics/health export tests (PROFILE.md §11): Prometheus text that
parses and equals Runtime.profile(), the /healthz ok→stalled flip, the
scrape-during-run HTTP round-trip, observability-options jaxpr identity
(PR-4 style), and the doctor CLI against a live endpoint."""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from ponyc_tpu import Runtime, RuntimeOptions
from ponyc_tpu import metrics
from ponyc_tpu.metrics import parse_prometheus, prometheus_text
from ponyc_tpu.models import ring


def _opts(**kw):
    base = dict(mailbox_cap=8, batch=1, max_sends=1, msg_words=1,
                spill_cap=64, inject_slots=8)
    base.update(kw)
    return RuntimeOptions(**base)


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5.0) as r:
        return r.read().decode(), r.headers.get("Content-Type", "")


# ----------------------------------------------- counters == profile()

def test_prometheus_counters_match_profile(tmp_path):
    """ACCEPTANCE: scraping the metrics port of a live runtime yields
    Prometheus text whose counters equal Runtime.profile() — totals,
    per-behaviour runs, per-cohort queue-wait percentiles."""
    rt, ids = ring.build(8, _opts(analysis=1, metrics_port=0,
                                  analysis_path=str(tmp_path / "a.csv")))
    port = rt._metrics.port
    rt.send(int(ids[0]), ring.RingNode.token, 120)
    assert rt.run() == 0
    body, ctype = _get(port, "/metrics")
    assert ctype.startswith("text/plain")
    p = parse_prometheus(body)
    prof = rt.profile()
    t = prof["totals"]
    assert p[("pony_tpu_processed_total", ())] == t["processed"] == 120
    assert p[("pony_tpu_delivered_total", ())] == t["delivered"]
    assert p[("pony_tpu_rejected_total", ())] == t["rejected"]
    assert p[("pony_tpu_badmsg_total", ())] == t["badmsg"]
    assert p[("pony_tpu_deadletter_total", ())] == t["deadletter"]
    assert p[("pony_tpu_mutes_total", ())] == t["mutes"]
    assert p[("pony_tpu_behaviour_runs_total",
              (("behaviour", "RingNode.token"),))] \
        == prof["behaviours"]["RingNode.token"]["runs"]
    c = prof["cohorts"]["RingNode"]
    assert p[("pony_tpu_queue_wait_ticks",
              (("cohort", "RingNode"), ("quantile", "0.5")))] \
        == c["queue_wait_p50"]
    assert p[("pony_tpu_queue_wait_ticks",
              (("cohort", "RingNode"), ("quantile", "0.99")))] \
        == c["queue_wait_p99"]
    rl = rt.run_loop_stats()
    assert p[("pony_tpu_windows_total", ())] == rl["windows"]
    assert p[("pony_tpu_health", ())] == 1      # ok
    rt.stop()


def test_scrape_during_live_run(tmp_path):
    """/metrics and /healthz answer OVER HTTP while Runtime.run() is
    executing (the run loop pushes snapshots; the HTTP thread never
    touches the device)."""
    rt, ids = ring.build(8, _opts(analysis=1, metrics_port=0,
                                  analysis_path=str(tmp_path / "a.csv")))
    port = rt._metrics.port
    rt.send(int(ids[0]), ring.RingNode.token, 20000)
    got = []

    def scraper():
        while not done.is_set():
            try:
                hz = json.loads(_get(port, "/healthz")[0])
                mx = parse_prometheus(_get(port, "/metrics")[0])
                got.append((hz["status"], mx))
            except (OSError, urllib.error.URLError):
                pass
            time.sleep(0.01)

    done = threading.Event()
    t = threading.Thread(target=scraper, daemon=True)
    t.start()
    assert rt.run() == 0
    done.set()
    t.join(timeout=5.0)
    assert got, "no successful scrape during the run"
    statuses = {s for s, _ in got}
    assert statuses <= {"ok"}                  # a healthy run stays ok
    final = parse_prometheus(_get(port, "/metrics")[0])
    assert final[("pony_tpu_processed_total", ())] \
        == rt.profile()["totals"]["processed"] == 20000
    # mid-run scrapes are monotone prefixes of the final truth
    mid = [m.get(("pony_tpu_processed_total", ()), 0) for _, m in got]
    assert all(0 <= v <= 20000 for v in mid)
    rt.stop()


def test_healthz_flips_ok_to_stalled(tmp_path):
    """The /healthz verdict flips ok → stalled when the watchdog trips
    (and carries the reason), without the HTTP surface going down."""
    rt, ids = ring.build(8, _opts(analysis=1, metrics_port=0,
                                  watchdog_s=30.0,
                                  analysis_path=str(tmp_path / "a.csv")))
    port = rt._metrics.port
    rt.send(int(ids[0]), ring.RingNode.token, 10)
    rt.run()
    hz = json.loads(_get(port, "/healthz")[0])
    assert hz["status"] == "ok" and hz["watchdog"] is not None
    # Simulate the trip the monitor thread would record for a wedged
    # phase (trip() itself also interrupts the main thread — us: so the
    # live monitor, which polls the same stamp every 0.25 s and would
    # trip on the faked one too, is stopped first. Seen in PR 31: its
    # SIGINT took the xdist worker down "by keyboard-interrupt", and the
    # session with it, rc 2 with 190 tests not run).
    rt._watchdog.close()
    rt._watchdog.join(5.0)
    rt._wd_stamp = ("in-flight", 99, time.monotonic() - 120.0)
    trip = rt._watchdog.check()
    assert trip is not None
    rt._watchdog.tripped = trip
    hz2 = json.loads(_get(port, "/healthz")[0])
    assert hz2["status"] == "stalled"
    assert "in-flight" in hz2["reason"]
    mx = parse_prometheus(_get(port, "/metrics")[0])
    assert mx[("pony_tpu_health", ())] == 0
    rt._watchdog.tripped = None                # un-wedge: flips back
    rt._wd_stamp = ("idle", 100, time.monotonic())
    assert json.loads(_get(port, "/healthz")[0])["status"] == "ok"
    rt.stop()


def test_healthz_degraded_on_coded_errors(tmp_path):
    rt, ids = ring.build(8, _opts(analysis=1, metrics_port=0,
                                  analysis_path=str(tmp_path / "a.csv")))
    port = rt._metrics.port
    rt.send(int(ids[0]), ring.RingNode.token, 10)
    rt.run()
    rt._error_counts[("SpillOverflowError", 2)] += 1
    rt._metrics.update_now(rt)
    hz = json.loads(_get(port, "/healthz")[0])
    assert hz["status"] == "degraded"
    assert "SpillOverflowError" in hz["reason"]
    mx = parse_prometheus(_get(port, "/metrics")[0])
    assert mx[("pony_tpu_errors_total",
               (("class", "SpillOverflowError"), ("code", "2")))] == 1
    assert mx[("pony_tpu_health", ())] == 0.5
    rt.stop()


# ----------------------------------------------------- server plumbing

def test_http_surface_shapes(tmp_path):
    rt, _ids = ring.build(8, _opts(metrics_port=0,
                                   analysis_path=str(tmp_path / "a.csv")))
    port = rt._metrics.port
    body, ctype = _get(port, "/healthz")
    assert ctype.startswith("application/json")
    hz = json.loads(body)
    assert set(hz) >= {"status", "reason", "phase", "steps"}
    # the root path serves metrics (scrape-config convenience)
    assert "# TYPE pony_tpu_steps_total counter" in _get(port, "/")[0]
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(port, "/nope")
    assert ei.value.code == 404
    rt.stop()
    # stop() tears the server down: the port stops answering
    with pytest.raises((OSError, urllib.error.URLError)):
        _get(port, "/healthz")
    assert rt._metrics is None


def test_snapshot_degrades_at_analysis0(tmp_path):
    """metrics_port works at analysis=0: totals come from host-side
    accounting (no profiler lanes to read)."""
    rt, ids = ring.build(8, _opts(analysis=0, metrics_port=0,
                                  analysis_path=str(tmp_path / "a.csv")))
    rt.send(int(ids[0]), ring.RingNode.token, 40)
    rt.run()
    p = parse_prometheus(_get(rt._metrics.port, "/metrics")[0])
    assert p[("pony_tpu_processed_total", ())] == 40
    assert ("pony_tpu_behaviour_runs_total",
            (("behaviour", "RingNode.token"),)) not in p
    rt.stop()


def test_parse_prometheus_and_escaping():
    snap = {"totals": {"processed": 3}, "steps": 7,
            "behaviours": {'T"x\\y.beh': {"runs": 2, "rejected": 0}},
            "errors": [{"class": "PonyError", "code": 9, "count": 4}]}
    text = prometheus_text(snap, {"status": "degraded"})
    p = parse_prometheus(text)
    assert p[("pony_tpu_processed_total", ())] == 3
    assert p[("pony_tpu_errors_total",
              (("class", "PonyError"), ("code", "9")))] == 4
    assert p[("pony_tpu_health", ())] == 0.5
    # label values round-trip through the escaper
    assert any(k[0] == "pony_tpu_behaviour_runs_total" for k in p)


# ------------------------------------------------------- jaxpr identity

def test_observability_options_keep_jaxpr_identity():
    """ACCEPTANCE (PR-4 style): with metrics_port=None and analysis=0,
    a build with the observability knobs set (flight ring size,
    watchdog deadline) lowers to a step jaxpr BIT-IDENTICAL to the
    default build — the whole layer is host-side."""
    import jax
    import jax.numpy as jnp

    from ponyc_tpu.program import Program
    from ponyc_tpu.runtime import engine
    from ponyc_tpu.runtime.state import init_state

    def build(**kw):
        opts = _opts(analysis=0, **kw)
        prog = Program(opts)
        prog.declare(ring.RingNode, 8)
        prog.finalize()
        st = init_state(prog, opts)
        step = engine.build_step(prog, opts)
        k = opts.inject_slots
        inj_t = jnp.full((k,), -1, jnp.int32)
        inj_w = jnp.zeros((1 + opts.msg_words, k), jnp.int32)
        return str(jax.make_jaxpr(step)(st, inj_t, inj_w))

    baseline = build()
    assert build(flight_windows=4, watchdog_s=2.5) == baseline


# ----------------------------------------------------------- doctor CLI

def test_doctor_cli_live_endpoint(tmp_path, capsys):
    from ponyc_tpu.__main__ import main as cli_main
    rt, ids = ring.build(8, _opts(analysis=1, metrics_port=0,
                                  analysis_path=str(tmp_path / "a.csv")))
    port = rt._metrics.port
    rt.send(int(ids[0]), ring.RingNode.token, 15)
    rt.run()
    assert cli_main(["doctor", f"127.0.0.1:{port}"]) == 0
    out = capsys.readouterr().out
    assert out.startswith("OK:")
    assert "pony_tpu_processed_total = 15" in out
    # stalled verdict exits 1
    rt._wd_stamp = ("in-flight", 1, time.monotonic() - 1e5)
    rt._watchdog_dummy = None
    rt.stop()
    # unreachable endpoint is a usage-ish failure (2)
    assert cli_main(["doctor", f"127.0.0.1:{port}"]) == 2


def test_metrics_option_validation():
    with pytest.raises(ValueError, match="metrics_port"):
        RuntimeOptions(metrics_port=70000)
    with pytest.raises(ValueError, match="metrics_port"):
        RuntimeOptions(metrics_port=-1)
