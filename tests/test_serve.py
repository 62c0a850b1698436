"""Serving front door tests (ISSUE 9, PROFILE.md §13): framing
round-trips incl. split reads and malformed frames, admission shed
under synthetic qw_p99 pressure, graceful-drain-loses-nothing, slow
consumers not stalling neighbours, the net-pending-bytes health flip,
and (slow, subprocess) SIGTERM drain + supervisor-restart reconnect."""

import json
import os
import signal
import socket
import struct
import sys
import threading
import time

import numpy as np
import pytest

import _child
from ponyc_tpu import loadgen, serve
from ponyc_tpu.errors import ERROR_CODES
from ponyc_tpu.serve import (ST_BADFRAME, ST_BUSY, ST_DEADLINE, ST_OK,
                             AdmissionController, FrameError, Framer,
                             encode_reply, encode_request)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- framing ------------------------------------------------------------

def test_frame_roundtrip_and_split_reads():
    """Frames survive arbitrary chunking: byte-by-byte feeds and many
    frames coalesced into one chunk both decode to the same words."""
    frames = [encode_request(i, 50 * i, [i * 3, -i]) for i in range(9)]
    blob = b"".join(frames)
    # One-byte drip.
    f = Framer(max_words=8)
    got = []
    for i in range(len(blob)):
        got += [w.tolist() for w in f.feed(blob[i:i + 1])]
    assert got == [[i, 50 * i, i * 3, -i] for i in range(9)]
    # All at once.
    f2 = Framer(max_words=8)
    got2 = [w.tolist() for w in f2.feed(blob)]
    assert got2 == got
    # Replies too, incl. negative words (i32).
    f3 = Framer()
    (w,) = f3.feed(encode_reply(7, ST_OK, [-5]))
    assert w.tolist() == [7, 0, -5]


@pytest.mark.parametrize("body_len", [0, 3, 5, 4 * 100])
def test_framer_rejects_malformed(body_len):
    """Zero-length, non-word and oversized bodies raise FrameError
    (the stream is desynced; the server closes the connection)."""
    f = Framer(max_words=64)
    raw = struct.pack(">I", body_len) + b"\x00" * body_len
    with pytest.raises(FrameError):
        f.feed(raw)


def test_status_codes_are_error_codes():
    """Wire statuses ARE the append-only ERROR_CODES values — one
    numbering for alerts, postmortems and replies."""
    assert ST_BADFRAME == ERROR_CODES["FrameError"] == 12
    assert ST_BUSY == ERROR_CODES["ServeBusyError"] == 13
    assert ST_DEADLINE == ERROR_CODES["ServeDeadlineError"] == 14
    assert serve.FrameError.code == 12
    assert serve.ServeBusyError.code == 13
    assert serve.ServeDeadlineError.code == 14


# ---- admission controller (pure decision logic) -------------------------

def test_admission_controller_mimd():
    ac = AdmissionController(lo=2, hi=64, initial=16)
    # qw_p99 past the window: shrink x1/2 per observation, floored.
    for expect in (8, 4, 2, 2):
        ac.observe(qw_p99=100, window=8, muted=0, spill_frac=0.0,
                   used=16)
        assert ac.limit == expect and ac.state == "shrink"
    # Quiet + fully used: grow x2 toward hi.
    for expect in (4, 8, 16, 32, 64, 64):
        ac.observe(qw_p99=0, window=8, muted=0, spill_frac=0.0,
                   used=ac.limit)
        assert ac.limit == expect
    assert ac.state == "steady"       # at hi: hold
    # Mute pressure and spill occupancy shrink too.
    ac.observe(qw_p99=0, window=8, muted=3, spill_frac=0.0, used=1)
    assert ac.limit == 32 and ac.state == "shrink"
    ac.observe(qw_p99=0, window=8, muted=0, spill_frac=0.9, used=1)
    assert ac.limit == 16
    # Quiet but under-used: hold (no evidence the edge is the limit).
    ac.observe(qw_p99=0, window=8, muted=0, spill_frac=0.0, used=3)
    assert ac.limit == 16 and ac.state == "steady"
    snap = ac.snapshot()
    assert snap["shrinks"] == 6 and snap["grows"] == 5


def test_admission_controller_validates_bounds():
    with pytest.raises(ValueError):
        AdmissionController(lo=0, hi=4)
    with pytest.raises(ValueError):
        AdmissionController(lo=8, hi=4)


# ---- end-to-end over real sockets ---------------------------------------

def _run_with_client(rt, server, client_fn, timeout_s=60.0):
    """Run rt.run() on this thread while client_fn drives sockets from
    a worker thread; begin_drain() fires when the client finishes (so
    run() exits via the drain path)."""
    out = {}

    def body():
        try:
            out["result"] = client_fn()
        except Exception as e:              # noqa: BLE001
            out["error"] = e
        finally:
            server.begin_drain()

    t = threading.Thread(target=body, daemon=True)
    t.start()
    code = rt.run()
    t.join(timeout=timeout_s)
    assert not t.is_alive(), "client thread wedged"
    if "error" in out:
        raise out["error"]
    return code, out.get("result")


def _build(n_workers=8, **server_kw):
    opts = serve.default_options(n_workers)
    rt, server = serve.build(n_workers, opts, **server_kw)
    port = server.listen("127.0.0.1", 0)
    return rt, server, port


def test_request_reply_roundtrip_and_values():
    """ACCEPTANCE: socket → frame → admission → bulk_send batch →
    device worker → egress → framed reply, values verified (2*x+1),
    every request answered, nothing shed at gentle load."""
    rt, server, port = _build(8)
    # The first window pays the XLA compile: the client's "no progress
    # for timeout_s, bail" must cover it on a loaded host too (its 10 s
    # default did not beside a second suite, PR 31: 4 of 80 answered).
    code, res = _run_with_client(
        rt, server, lambda: loadgen.run_load(
            "127.0.0.1", port, conns=2, depth=2, requests=40,
            timeout_s=60.0))
    assert code == 0
    assert res["ok"] == res["sent"] == 80
    assert res["bad_value"] == 0 and res["unanswered"] == 0
    st = server.stats()
    assert st["replied"] == 80 and st["shed_total"] == 0
    assert st["batches"] >= 1 and st["submitted"] == 80
    # Worker-side evidence: the device cohort really served them.
    served = int(rt.cohort_state(serve.ServeWorker)["served"].sum())
    assert served == 80
    rt.stop()


def test_malformed_frame_gets_badframe_and_close():
    """A non-word body draws a BADFRAME(-1) reply, counts in
    rt._error_counts under code 12, and the connection closes; a well-
    framed wrong-arity request draws BADFRAME and KEEPS the conn."""
    rt, server, port = _build(4)

    def client():
        s = socket.create_connection(("127.0.0.1", port), timeout=10)
        s.sendall(struct.pack(">I", 3) + b"\xff\xff\xff")
        f = Framer()
        words = None
        while words is None:
            data = s.recv(4096)
            if not data:
                break
            for w in f.feed(data):
                words = w
        eof = s.recv(4096) if words is not None else b""
        s.close()
        # Arity error on a fresh conn: reply carries the req id, conn
        # survives for a follow-up valid request.
        s2 = socket.create_connection(("127.0.0.1", port), timeout=10)
        s2.sendall(encode_request(5, 0, [1, 2, 3]))   # 3 words != 1
        f2 = Framer()
        got = []
        while len(got) < 1:
            got += [w.tolist() for w in f2.feed(s2.recv(4096))]
        s2.sendall(encode_request(6, 0, [10]))
        while len(got) < 2:
            got += [w.tolist() for w in f2.feed(s2.recv(4096))]
        s2.close()
        return words.tolist(), eof, got

    code, (bad, eof, got) = _run_with_client(rt, server, client)
    assert code == 0
    assert bad == [-1, ST_BADFRAME]
    assert eof == b""                      # server closed the stream
    assert got[0] == [5, ST_BADFRAME]
    assert got[1] == [6, ST_OK, 21]
    assert rt._error_counts[("FrameError", 12)] >= 2
    assert server.stats()["badframe"] == 2
    rt.stop()


def test_admission_shed_under_synthetic_qw_pressure():
    """Synthetic qw_p99 pressure (the device's vote, injected in place
    of the retired aux) collapses the admission limit to lo; offered
    concurrency past the limit sheds BUSY at the edge while admitted
    requests still complete — the rings never see the overload."""
    rt, server, port = _build(8, admit_lo=1)

    class FakeAux:
        qw_p99 = np.int32(1 << 20)        # astronomically past window
        n_muted_now = np.int32(0)

    orig_observe = server._observe

    def pressured_observe(rt_, now):
        rt_._last_aux = FakeAux()
        orig_observe(rt_, now)
    server._observe = pressured_observe

    code, res = _run_with_client(
        rt, server, lambda: loadgen.run_load(
            "127.0.0.1", port, conns=2, depth=16, requests=60,
            busy_backoff_s=0.002))
    assert code == 0
    assert server.admission.limit == 1            # collapsed to lo
    assert server.admission.shrinks >= 3
    assert res["busy"] > 0, "nothing shed under pressure"
    assert res["ok"] > 0, "admitted requests must still complete"
    assert res["bad_value"] == 0 and res["unanswered"] == 0
    st = server.stats()
    assert st["shed"]["busy"] == res["busy"]
    # The device never saw more than the collapsed limit at once.
    assert rt._error_counts.get(("SpillOverflowError", 2), 0) == 0
    rt.stop()


def test_real_overload_at_twice_capacity_sheds_at_the_edge():
    """No synthetic vote: a gentle closed loop measures what the
    service answers, then conns x depth far past the worker pool offer
    at least twice that for a second. The front door sheds BUSY at the
    edge, admitted requests complete with p50 < p99, every frame is
    answered, and no ring ever reaches a sticky failure."""
    workers = 16
    rt, server, port = _build(workers)

    def client():
        calib = loadgen.run_load("127.0.0.1", port, conns=2, depth=2,
                                 requests=30, timeout_s=60.0)
        load = loadgen.run_load("127.0.0.1", port, conns=4,
                                depth=4 * workers, requests=1 << 30,
                                duration_s=1.0, busy_backoff_s=0.005)
        return calib, load

    code, (calib, load) = _run_with_client(rt, server, client)
    assert code == 0
    assert calib["unanswered"] == 0 and calib["goodput_rps"] > 0
    assert load["offered_rps"] >= 2.0 * calib["goodput_rps"]
    assert load["busy"] > 0, "overload was not shed"
    assert load["ok"] > 0 and load["goodput_rps"] > 0
    assert load["unanswered"] == 0 and load["bad_value"] == 0
    assert load["p99_us"] > load["p50_us"] > 0
    st = server.stats()
    assert st["drained"] and st["shed"]["busy"] > 0
    assert st["admission"]["limit"] >= 1
    assert st["batches"] >= 1 and st["submitted"] >= load["ok"]
    assert not [key for key in rt._error_counts if key[0] in (
        "SpillOverflowError", "SpawnCapacityError", "BlobCapacityError")]
    rt.stop()


def test_deadline_shed_and_expiry():
    """A deadline the measured service rate cannot meet sheds at the
    edge; a queued request whose deadline lapses is answered DEADLINE
    without touching a worker."""
    rt, server, port = _build(2)
    # Pin the admission limit high but make the service look slow.
    server._rate_ema = 10.0                # 10 rps measured

    def client():
        s = socket.create_connection(("127.0.0.1", port), timeout=10)
        f = Framer()
        # A 1 ms deadline with ~0 queue: est wait 0 — admitted. Then
        # stack enough 1 ms-deadline requests that est_wait > deadline.
        for i in range(30):
            s.sendall(encode_request(100 + i, 1, [i]))
        got = []
        t0 = time.monotonic()
        while len(got) < 30 and time.monotonic() - t0 < 30:
            data = s.recv(65536)
            if not data:
                break
            got += [w.tolist() for w in f.feed(data)]
        s.close()
        return got

    code, got = _run_with_client(rt, server, client)
    assert code == 0
    statuses = {w[1] for w in got}
    assert len(got) == 30                  # every request answered
    # With a 10 rps estimate and 1 ms deadlines, the queue beyond the
    # first request sheds (BUSY at admission or DEADLINE at expiry).
    assert statuses <= {ST_OK, ST_BUSY, ST_DEADLINE}
    assert statuses & {ST_BUSY, ST_DEADLINE}
    st = server.stats()
    assert st["shed"]["deadline"] + st["shed"]["busy"] > 0
    rt.stop()


def test_graceful_drain_loses_nothing():
    """ACCEPTANCE: begin_drain() mid-load — every request sent before
    the drain answered (OK for admitted, BUSY for post-drain frames),
    zero unanswered, the world exits 0 and the server reports
    drained."""
    rt, server, port = _build(8, drain_grace_s=0.3)
    drain_at = threading.Event()

    def client():
        stats = {}

        def stream():
            # stop_on_busy: the first BUSY (= the drain announcing
            # itself) quiesces the offered load, so every frame the
            # client sent is answered before the server closes. The
            # offered concurrency (3x2) stays under the admission
            # limit (8 workers) so no BUSY fires BEFORE the drain.
            # (timeout_s: the first window pays the XLA compile, which
            # beside a second suite outlasts the client's 10 s default
            # "no progress, bail" — PR 31: 6 replies, then silence.)
            stats["r"] = loadgen.run_load(
                "127.0.0.1", port, conns=3, depth=2,
                requests=1 << 30, duration_s=30.0, stop_on_busy=True,
                timeout_s=60.0)
        t = threading.Thread(target=stream, daemon=True)
        t.start()
        # Wait until traffic is demonstrably flowing (the first window
        # pays the XLA compile), then drain mid-stream.
        deadline = time.monotonic() + 60.0
        while server.c["replied"] < 20 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert server.c["replied"] >= 20, "no traffic before drain"
        drain_at.set()
        server.begin_drain()
        t.join(timeout=30.0)
        assert not t.is_alive()
        return stats["r"]

    code, res = _run_with_client(rt, server, client)
    assert code == 0
    assert res["ok"] > 0, "no requests served before the drain"
    assert res["busy"] > 0, "post-drain frames must get BUSY replies"
    # Zero lost replies: every sent request was answered.
    assert res["unanswered"] == 0
    assert res["ok"] + res["busy"] + res["deadline"] == res["sent"]
    st = server.stats()
    assert st["drained"] and st["draining"]
    assert st["inflight"] == 0 and st["queue"] == 0
    assert st["accepted"] == st["replied"] + st["reclaimed"] \
        + st["abandoned"] + st["shed"]["deadline"]
    rt.stop()


def test_slow_consumer_does_not_stall_neighbours():
    """One connection stops reading (tiny SO_RCVBUF + huge request
    burst) while another runs a normal closed loop: the normal client
    completes everything; the slow one is choked/backpressured, never
    the world."""
    rt, server, port = _build(8, pending_limit=2048)
    t0 = time.monotonic()

    def client():
        slow_done = threading.Event()
        fast_done = threading.Event()

        def slow():
            try:
                s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
                s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 2048)
                s.settimeout(10.0)
                s.connect(("127.0.0.1", port))
                for i in range(800):
                    s.sendall(encode_request(i + 1, 0, [i]))
                # Never reads its replies, and hangs up only once the
                # fast lane is through: a fixed 2 s nap ended before a
                # loaded machine had compiled the first window, and the
                # server's first write then met a reset connection.
                fast_done.wait(timeout=20.0)
                s.close()
            except OSError:
                pass                       # server may kill the conn
            finally:
                slow_done.set()

        ts = threading.Thread(target=slow, daemon=True)
        ts.start()
        fast = loadgen.run_load("127.0.0.1", port, conns=1, depth=2,
                                requests=60, busy_backoff_s=0.002)
        fast_done.set()
        slow_done.wait(timeout=30.0)
        return fast

    code, fast = _run_with_client(rt, server, client)
    assert code == 0
    assert fast["ok"] + fast["busy"] == fast["sent"] == 60
    assert fast["ok"] > 0 and fast["unanswered"] == 0
    # The fast lane stayed responsive while the slow conn backed up.
    assert time.monotonic() - t0 < 45.0
    st = server.stats()
    assert st["net_pending_bytes"] >= 0
    assert st["shed"]["choked"] > 0 or st["conns_killed_slow"] > 0 \
        or st["shed"]["busy"] > 0
    rt.stop()


# ---- metrics / health satellites ----------------------------------------

def test_net_pending_bytes_exported_and_degrades_health(tmp_path):
    """pony_tpu_net_pending_bytes rides /metrics; /healthz flips to
    degraded when the egress backlog grows monotonically across
    PENDING_WINDOW snapshots."""
    from ponyc_tpu import metrics as metrics_mod
    from ponyc_tpu.metrics import (PENDING_WINDOW, health,
                                   parse_prometheus, prometheus_text)
    rt, server, port = _build(4)
    rt2 = rt                   # metrics server rides the same runtime
    from ponyc_tpu.metrics import MetricsServer
    mx = MetricsServer(rt2, 0)
    rt2._metrics = mx
    mx.update_now(rt2)
    snap = mx._snap
    assert "net" in snap and snap["net"]["pending_bytes"] == 0
    assert "serving" in snap and snap["serving"]["conns"] == 0
    text = prometheus_text(snap, health(rt2))
    parsed = parse_prometheus(text)
    assert parsed[("pony_tpu_net_pending_bytes", ())] == 0
    assert parsed[("pony_tpu_serve_admit_limit", ())] \
        == server.admission.limit
    assert health(rt2)["status"] == "ok"
    # Fabricate a monotone backlog trail: degraded with the reason.
    mx._pending_hist.clear()
    for v in range(1, PENDING_WINDOW + 1):
        mx._pending_hist.append(v * 1024)
    hz = health(rt2)
    assert hz["status"] == "degraded"
    assert "egress backpressure" in hz["reason"]
    # A non-monotone trail recovers.
    mx._pending_hist.append(0)
    assert health(rt2)["status"] == "ok"
    mx.close()
    rt.stop()


def test_serving_block_in_postmortem():
    """Flight-recorder dumps carry the serving block and the doctor's
    verdict mentions shed rate for a crashed serving world."""
    from ponyc_tpu.flight import diagnose_postmortem
    rt, server, port = _build(2)
    server.c["frames"] += 10
    server.c["shed_busy"] += 4
    pm = rt._flight.postmortem("crash: test")
    assert pm["serving"]["frames"] == 10
    assert pm["serving"]["shed"]["busy"] == 4
    line, detail = diagnose_postmortem(pm)
    assert "serving:" in line and "shed_rate" in line
    assert "serving: frames=10" in detail
    rt.stop()


# ---- bridge satellite ----------------------------------------------------

def test_bridge_poll_survives_raising_callback():
    """A raising fd/timer callback is counted per (class, code) and
    recorded in the flight recorder instead of killing the run loop
    (ISSUE 9 satellite: the ingress tier lives on these callbacks)."""
    from ponyc_tpu import I32, Runtime, RuntimeOptions, actor, behaviour

    @actor
    class Quiet:
        HOST = True
        n: I32

        @behaviour
        def tick(self, st, kind: I32, arg: I32, flags: I32):
            return {**st, "n": st["n"] + 1}

    rt = Runtime(RuntimeOptions(mailbox_cap=8, batch=1, max_sends=1,
                                msg_words=3, inject_slots=8))
    rt.declare(Quiet, 1).start()
    rt.spawn(Quiet)
    br = rt.attach_bridge()
    fired = []

    def boom(ev):
        fired.append(ev)
        raise ValueError("callback exploded")

    sid = br.timer_callback(boom, 0.01, noisy=True)
    deadline = time.monotonic() + 20.0
    while not fired and time.monotonic() < deadline:
        rt.run(max_steps=5)
    br.unsubscribe(sid)
    assert fired, "timer callback never fired"
    assert rt._error_counts[("ValueError", 0)] >= 1
    kinds = [e["kind"] for e in rt._flight.events]
    assert "bridge_callback_error" in kinds
    # The loop survived: further runs still work.
    assert rt.run(max_steps=5) == 0
    rt.stop()


# ---- subprocess acceptance (SIGTERM drain; supervisor restart) ----------

SERVE_SCRIPT = """\
import os, sys
sys.path.insert(0, {root!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from ponyc_tpu import serve
sys.exit(serve.main(sys.argv[1:]))
"""


@pytest.mark.slow
def test_sigterm_drains_every_admitted_request(tmp_path):
    """CHAOS ACCEPTANCE: SIGTERM mid-load — the subprocess server
    answers every request sent before the drain (OK or BUSY), exits 0,
    and reports drained stats on stderr. Zero lost replies."""
    script = tmp_path / "serve_script.py"
    script.write_text(SERVE_SCRIPT.format(root=ROOT))
    with _child.popen(
            [sys.executable, str(script), "--workers", "8",
             "--drain-grace", "0.5"],
            stdout=_child.PIPE, stderr=_child.PIPE, text=True,
            env={"PYTHONPATH": ROOT}, cwd=tmp_path) as proc:
        # Wait for the "serving on host:port" line (the test's deadline
        # bounds the read).
        line = proc.stdout.readline()
        assert line.startswith("serving on"), (line, proc.stderr.read()
                                               if proc.poll() else "")
        port = int(line.strip().rsplit(":", 1)[1].split()[0])
        # Warm probe: the first window pays the XLA compile — require
        # end-to-end service before measuring the drain.
        warm = loadgen.run_load("127.0.0.1", port, conns=1, depth=1,
                                requests=5, timeout_s=60.0)
        assert warm["ok"] == 5, warm
        res = {}

        def stream():
            # 3x2 concurrent stays under the 8-worker admission limit,
            # so the first BUSY is the SIGTERM drain announcing itself.
            res["r"] = loadgen.run_load(
                "127.0.0.1", port, conns=3, depth=2,
                requests=1 << 30, duration_s=30.0, stop_on_busy=True)

        t = threading.Thread(target=stream, daemon=True)
        t.start()
        time.sleep(1.5)                    # traffic flowing
        proc.send_signal(signal.SIGTERM)
        err = _child.finish(proc, timeout=60).stderr
        t.join(timeout=30.0)
        assert not t.is_alive()
        r = res["r"]
        assert proc.returncode == 0, err
        assert r["ok"] > 0
        assert r["unanswered"] == 0, r     # zero lost replies
        assert r["ok"] + r["busy"] + r["deadline"] == r["sent"]
        assert r["bad_value"] == 0
        drained = [ln for ln in err.splitlines()
                   if ln.startswith("serve: drained ")]
        assert drained, err
        st = json.loads(drained[-1][len("serve: drained "):])
        assert st["drained"] and st["inflight"] == 0
        assert st["accepted"] == st["replied"]


WEDGE_SCRIPT = """\
import os, sys
sys.path.insert(0, {root!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from ponyc_tpu import serve, testing
marker = {marker!r}
if not os.path.exists(marker):
    # First life only: wedge the egress behaviour after a few replies
    # so the watchdog (code 7) fires and the supervisor restarts us.
    open(marker, "w").write("wedged")
    testing.wedge_behaviour(serve.Egress.done, at_dispatch=5,
                            sleep_s=600.0)
sys.exit(serve.main(sys.argv[1:]))
"""


@pytest.mark.slow
def test_supervisor_restart_reaccepts_connections(tmp_path):
    """CHAOS ACCEPTANCE: a wedged world trips the watchdog (code 7),
    `ponyc_tpu supervise` restarts the service from the checkpoint
    ring, the fixed port is re-bound and a reconnecting client is
    served by the second life."""
    port = 0
    with socket.socket() as s:             # reserve a fixed free port
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    marker = tmp_path / "wedged.marker"
    script = tmp_path / "wedge_serve.py"
    script.write_text(WEDGE_SCRIPT.format(root=ROOT,
                                          marker=str(marker)))
    prefix = str(tmp_path / "ring")
    with _child.popen(
            [sys.executable, "-m", "ponyc_tpu", "supervise",
             "--prefix", prefix, "--retries", "3", "--backoff", "0.1",
             str(script), "--port", str(port), "--workers", "4",
             "--ponywatchdog_s", "3", "--ponycheckpoint_every_s", "0.2",
             f"--ponycheckpoint_path={prefix}"],
            stdout=_child.PIPE, stderr=_child.PIPE, text=True,
            env={"PYTHONPATH": ROOT}, cwd=tmp_path) as proc:
        # Under conftest's per-test deadline: both lives compile (~15 s
        # each), the wedge trips a 3 s watchdog.
        probe_s = 100.0
        # Probe state machine: wait for life 1 to serve (up), drive it
        # into the wedge (replies stop mid-probe), then keep
        # reconnecting until life 2 serves a full round again.
        deadline = time.monotonic() + probe_s
        phase = "wait_up"
        while time.monotonic() < deadline and phase != "recovered":
            if proc.poll() is not None:
                break
            r = loadgen.run_load("127.0.0.1", port, conns=1, depth=1,
                                 requests=3, timeout_s=3.0)
            full = r["ok"] == 3 and r["bad_value"] == 0
            if phase == "wait_up" and full:
                phase = "up"
            elif phase == "up" and not full:
                phase = "wedged"           # the 5th egress dispatch hung
            elif phase == "wedged" and full:
                phase = "recovered"        # life 2 answered end to end
                break
            time.sleep(0.5)
        assert marker.exists(), "the wedge never armed"
        assert phase == "recovered", \
            f"no round-trip after the wedged life (stuck at {phase})"
        # Stop the whole tree (supervisor + supervised child share the
        # group `_child.popen` made; the supervisor does not forward
        # signals). One that is still there 60 s later is killed.
        _child.kill_group(proc, signal.SIGTERM)
        try:
            err = _child.finish(proc, timeout=60).stderr
        except _child.ChildTimeout as e:
            err = e.stderr
        # The supervisor logged the code-7 wedged life's restart.
        assert "restarting" in err or "recovered after" in err, err
