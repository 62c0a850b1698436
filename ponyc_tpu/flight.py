"""Flight recorder + stall watchdog — the runtime's always-on black box
(PROFILE.md §11; ≙ the fork's runtime-analysis/telemetry machinery run
in the always-on, crash-evidence posture a serving runtime needs, not
the opt-in profiling one).

The per-behaviour profiler (PR 4) and causal tracing (PR 6) made the
runtime *introspectable*; nothing made it *operable*: a wedged window
or a backend init that never returned produced no diagnosis. Two
host-side pieces fix that:

- **FlightRecorder** — a bounded ring retaining the last
  ``RuntimeOptions(flight_windows)`` retired windows (the control
  scalars the run loop ALREADY fetched per retire: aux flags, counters,
  ticks/budget, host gap, controller snapshot), plus bounded rings of
  runtime events (GC passes, coded errors) and recent host-cohort mail.
  Recording is a deque append of host ints — negligible, and nothing
  here feeds the traced step: at analysis=0 the step jaxpr stays
  bit-identical to a recorder-free build (tests/test_metrics.py
  asserts it PR-4 style). The ring dumps as a structured postmortem
  (``<analysis_path>.postmortem.json`` + human text on stderr) on
  crash, on SIGQUIT, on a watchdog trip, and on
  ``Runtime.stop(postmortem=True)``.

- **Watchdog** — a monitor thread that knows the pipelined run loop's
  phases (backend-init / dispatching / in-flight / host-work /
  quiescent / idle) via the cheap epoch stamps runtime.py writes at
  every transition (one tuple assignment). A phase that makes no
  progress stamp within ``RuntimeOptions(watchdog_s)`` — scaled by the
  PR 5 controller's current/initial window ratio, so a legitimately
  grown window is not misread as a stall — trips: the flight recorder
  dumps, a one-line doctor diagnosis lands on stderr, and the main
  thread is interrupted so Runtime.run()/start() raise an int-coded
  ``errors.PonyStallError`` instead of hanging forever. Quiescent/idle
  phases never trip (a runtime waiting on external events is healthy).

``python -m ponyc_tpu doctor --postmortem FILE`` renders a dump into a
diagnosis (``diagnose_postmortem`` below).
"""

from __future__ import annotations

import collections
import json
import os
import sys
import threading
import time
import weakref
from typing import Any, Dict, Optional, Tuple

POSTMORTEM_VERSION = 1

# Ring capacities for the non-window lanes: small fixed bounds — the
# recorder must never grow with run length.
EVENT_RING = 128
HOST_MAIL_RING = 32

# Phases the watchdog arms on. "quiescent" (waiting on external events)
# and "idle" (no run() in progress) are healthy steady states.
ARMED_PHASES = frozenset({"backend-init", "dispatching", "in-flight",
                          "host-work"})

# Deadline multiplier for COLD device phases (backend init and the
# first window before any retire): the first dispatch pays trace + XLA
# compile — tens of seconds is legitimate there (about a minute for a
# 1M-actor window on a v5e, PERF.md) and must not read as a stall
# under a deadline sized for steady-state windows.
COLD_FACTOR = 10.0


_latest: Optional["weakref.ref[FlightRecorder]"] = None


def latest() -> Optional["FlightRecorder"]:
    """The recorder of the runtime most recently started in this
    process, or None (none started, or that runtime is gone: the
    reference is weak and keeps nothing alive). For a signal handler, a
    REPL or a benchmark reader that must reach the black box without
    holding `rt`."""
    return _latest() if _latest is not None else None


def env_snapshot() -> Dict[str, Any]:
    """Probed-environment snapshot for postmortems: accelerator-related
    env vars (secret-filtered), libtpu importability, device nodes —
    the block that makes a backend-init failure diagnosable from the
    record alone (every flight-recorder dump carries it)."""
    import importlib.util
    env = {k: v for k, v in sorted(os.environ.items())
           if k.startswith(("TPU", "JAX", "LIBTPU", "PJRT", "XLA"))
           and "KEY" not in k and "TOKEN" not in k and "SECRET" not in k}
    details: Dict[str, Any] = {
        "env": env,
        "libtpu_importable":
            importlib.util.find_spec("libtpu") is not None}
    for dev in ("/dev/accel0", "/dev/vfio"):
        details[f"dev:{dev}"] = os.path.exists(dev)
    return details


class FlightRecorder:
    """Per-runtime bounded black box. All writers run on the run-loop
    thread (window/gc/host-mail records) or the main thread; dump() may
    additionally run on the watchdog thread — deque appends and
    wholesale reads are safe under the GIL, and a postmortem taken
    mid-append only ever misses the newest record."""

    def __init__(self, rt, capacity: int = 64):
        self.rt = rt
        self.windows: collections.deque = collections.deque(
            maxlen=max(1, int(capacity)))
        self.events: collections.deque = collections.deque(
            maxlen=EVENT_RING)
        self.host_mail: collections.deque = collections.deque(
            maxlen=HOST_MAIL_RING)
        self.t0 = time.time()
        self.last_dump: Optional[str] = None    # newest postmortem path
        self.dumps = 0
        global _latest
        _latest = weakref.ref(self)

    # -- recording (hot-ish path: host ints only, one deque append) --
    def window(self, step: int, ticks: int, budget: int, gap_us: float,
               pipelined: bool, aux, wall_ms: float = 0.0,
               wait_ms: float = 0.0, since_prev_ms: float = 0.0,
               dispatch_ms: float = 0.0,
               outside_ms: Optional[Dict[str, float]] = None) -> None:
        """One retired window's facts. `aux` is the already-fetched
        host-side StepAux (numpy scalars) — the recorder converts, the
        run loop pays no extra device traffic. `wall_ms`: dispatch start
        to retire returned; `wait_ms`: the part of it the host was
        blocked on the device (`pony:wait`); `since_prev_ms`: the
        previous record's retire to this dispatch's start, across run()
        calls too. A pipelined window has since_prev_ms 0 and a wall_ms
        counted from the previous retire, so since_prev_ms + wall_ms of
        consecutive records tile the wall clock. `dispatch_ms`: the
        window's `pony:dispatching`, start of the dispatch to the
        launch's return — the first stretch of a sync-point window's
        wall_ms; a pipelined window's ran inside the record before.
        `outside_ms`: {API phase: ms} (`runtime.API_PHASES`: a
        counter() read, a cohort_state()) for the calls that ran in
        since_prev_ms — its itemisation; what is left of it is run()'s
        own exit and entry and the caller's."""
        self.windows.append({
            "t_ms": round((time.time() - self.t0) * 1e3, 3),
            "step": int(step), "ticks": int(ticks),
            "budget": int(budget), "gap_us": round(float(gap_us), 1),
            "wall_ms": round(float(wall_ms), 4),
            "wait_ms": round(float(wait_ms), 4),
            "since_prev_ms": round(float(since_prev_ms), 4),
            "dispatch_ms": round(float(dispatch_ms), 4),
            "outside_ms": {p: round(float(ms), 4)
                           for p, ms in (outside_ms or {}).items()},
            "pipelined": bool(pipelined),
            "processed": int(aux.n_processed) & 0xFFFFFFFF,
            "delivered": int(aux.n_delivered) & 0xFFFFFFFF,
            "occ_sum": int(aux.occ_sum), "occ_max": int(aux.occ_max),
            "qw_p99": int(aux.qw_p99),
            "muted_now": int(aux.n_muted_now),
            # cumulative ticks delivered over the list's prefix, where
            # the window holds that path (StepAux.lists)
            **{name: int(n) & 0xFFFFFFFF
               for name, n in aux.lists.items()},
            "flags": {
                "device_pending": bool(aux.device_pending),
                "host_pending": bool(aux.host_pending),
                "exit": bool(aux.exit_flag),
                "any_muted": bool(aux.any_muted),
                "spill_overflow": bool(aux.spill_overflow),
                "spawn_fail": bool(aux.spawn_fail),
                "blob_fail": bool(aux.blob_fail),
                "blob_budget_fail": bool(aux.blob_budget_fail),
            },
        })

    def event(self, kind: str, **fields) -> None:
        """A runtime event (gc pass, coded error, watchdog arm/trip)."""
        self.events.append({
            "t_ms": round((time.time() - self.t0) * 1e3, 3),
            "step": int(getattr(self.rt, "steps_run", 0)),
            "kind": kind, **fields})

    def mail(self, actor_id: int, behaviour: str) -> None:
        """One host-cohort dispatch (the 'recent host mail' lane)."""
        self.host_mail.append({
            "t_ms": round((time.time() - self.t0) * 1e3, 3),
            "step": int(getattr(self.rt, "steps_run", 0)),
            "actor": int(actor_id), "behaviour": behaviour})

    # -- snapshotting / dumping --
    def postmortem(self, reason: str, **extra) -> Dict[str, Any]:
        """The structured dump: reason + the rings + runtime/host facts.
        Everything in it is JSON-serialisable host state — building it
        never touches the device (a postmortem of a wedged device must
        not block on the device)."""
        rt = self.rt
        import dataclasses
        ctrl = getattr(rt, "_controller", None)
        wd = getattr(rt, "_watchdog", None)
        phase, epoch, t = getattr(rt, "_wd_stamp", ("?", 0, 0.0))
        pm: Dict[str, Any] = {
            "version": POSTMORTEM_VERSION,
            "reason": reason,
            "time": time.time(),
            "uptime_s": round(time.time() - self.t0, 3),
            "pid": os.getpid(),
            "steps_run": int(getattr(rt, "steps_run", 0)),
            "phase": {"name": phase, "epoch": int(epoch),
                      "age_s": round(max(0.0, time.monotonic() - t), 3)
                      if t else None},
            "windows": list(self.windows),
            "events": list(self.events),
            "host_mail": list(self.host_mail),
            "queues": {"inject": len(getattr(rt, "_inject_q", ())),
                       "fast": len(getattr(rt, "_host_fast_q", ()))},
            "totals": {k: int(v)
                       for k, v in getattr(rt, "totals", {}).items()},
            "errors": [{"class": cls, "code": int(code), "count": int(n)}
                       for (cls, code), n in sorted(
                           getattr(rt, "_error_counts", {}).items())],
            # Durable-worlds evidence (ISSUE 8): where the newest
            # restorable checkpoint lives — the first thing an operator
            # (or the supervisor) needs from a crash dump.
            "checkpoint": (rt._ckpt.info()
                           if getattr(rt, "_ckpt", None) is not None
                           else None),
            "controller": (None if ctrl is None else {
                **ctrl.snapshot(),
                "recent": ctrl.recent_decisions()}),
            # Serving front door (ISSUE 9): shed rate / queue depth /
            # admission limit / egress backlog — the overload half of
            # a service postmortem (None when no Server is attached).
            "serving": (rt._serve.stats()
                        if getattr(rt, "_serve", None) is not None
                        else None),
            "watchdog": (None if wd is None else wd.snapshot()),
            # The compiler's cost record: the costs.capture memo where
            # a capture ran — a host attribute. None before one
            # (readers must .get()).
            "measured": getattr(rt, "_costs", None),
            "options": dataclasses.asdict(rt.opts)
            if getattr(rt, "opts", None) is not None else {},
            "env": env_snapshot(),
        }
        pm.update(extra)
        return pm

    def dump(self, reason: str, path: Optional[str] = None,
             out=None, **extra) -> Tuple[str, str]:
        """Write ``<analysis_path>.postmortem.json`` (or `path`) and
        print the human rendering to stderr (or `out`). Returns
        (path, text). Never raises — a failing dump on the way down
        must not mask the original crash."""
        pm = self.postmortem(reason, **extra)
        if path is None:
            path = self.rt.opts.analysis_path + ".postmortem.json"
        text = render_postmortem(pm)
        try:
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(pm, f, indent=1)
            os.replace(tmp, path)    # readers never see a half dump
        except OSError as e:
            text += f"\n(postmortem file write failed: {e})"
            path = ""
        try:
            print(text, file=out or sys.stderr)
        except Exception:      # noqa: BLE001 — closed stderr on teardown
            pass
        self.last_dump = path or None
        self.dumps += 1
        return path, text


# ---- the stall watchdog ---------------------------------------------------

class Watchdog(threading.Thread):
    """Monitor thread converting a silent hang into evidence + an
    int-coded error. Reads only host attributes (the phase stamp tuple,
    the controller's window int) — it can observe a runtime whose
    device is wedged solid."""

    def __init__(self, rt, deadline_s: float):
        super().__init__(name="pony-tpu-watchdog", daemon=True)
        self.rt = rt
        self.deadline_s = float(deadline_s)
        self.tripped: Optional[Dict[str, Any]] = None
        self._closed = threading.Event()   # not `_stop`: Thread.join calls that
        self._main_ident = threading.main_thread().ident

    def effective_deadline(self, phase: Optional[str] = None) -> float:
        """The configured deadline scaled by (a) how far the adaptive
        controller has grown the window past its initial value — a
        1024-tick window legitimately takes longer than the 4-tick one
        the deadline was calibrated against — and (b) COLD_FACTOR for
        device phases before the first retire (trace + XLA compile)."""
        base = self.deadline_s
        ctrl = getattr(self.rt, "_controller", None)
        loaded = int(getattr(self.rt, "_qi_loaded", 0) or 0)
        if ctrl is not None and loaded > 0:
            base *= max(1.0, ctrl.window / loaded)
        if phase in ("backend-init", "dispatching", "in-flight") \
                and int(getattr(self.rt, "_rl_windows", 0)) == 0:
            base *= COLD_FACTOR
        return base

    def check(self, now: Optional[float] = None) -> Optional[Dict[str, Any]]:
        """One deadline evaluation (pure in the stamp + clock): the trip
        record when the armed phase's stamp is older than the effective
        deadline, else None. Exposed for tests — the thread loop below
        is just this on a timer."""
        now = time.monotonic() if now is None else now
        phase, epoch, t = getattr(self.rt, "_wd_stamp", ("idle", 0, now))
        if phase not in ARMED_PHASES:
            return None
        deadline = self.effective_deadline(phase)
        age = now - t
        if age <= deadline:
            return None
        return {"phase": phase, "epoch": int(epoch),
                "age_s": round(age, 3),
                "deadline_s": round(deadline, 3),
                "configured_s": self.deadline_s}

    def snapshot(self) -> Dict[str, Any]:
        return {"deadline_s": self.deadline_s,
                "effective_deadline_s": round(self.effective_deadline(), 3),
                "tripped": self.tripped}

    def run(self) -> None:
        poll = max(0.01, min(0.25, self.deadline_s / 4.0))
        while not self._closed.wait(poll):
            trip = self.check()
            if trip is not None:
                self.trip(trip)
                return

    def trip(self, info: Dict[str, Any]) -> None:
        """Dump the postmortem, diagnose on stderr, interrupt the main
        thread so run()/start() convert the pending KeyboardInterrupt
        into PonyStallError. A truly wedged C call (a hung backend
        never returning) cannot be unblocked host-side — the dump on
        disk is the value there; the interrupt lands the moment the
        call (or the signal mask across the donation region) yields."""
        self.tripped = info
        fr = getattr(self.rt, "_flight", None)
        path = ""
        if fr is not None:
            fr.event("watchdog_trip", **info)
            path, _ = fr.dump(
                reason=f"watchdog: phase {info['phase']!r} made no "
                       f"progress for {info['age_s']}s "
                       f"(deadline {info['deadline_s']}s)")
            info["postmortem"] = path
        print("ponyc_tpu doctor: STALLED — phase "
              f"{info['phase']!r} silent for {info['age_s']}s "
              f"(deadline {info['deadline_s']}s); postmortem: "
              f"{path or '(unwritten)'}", file=sys.stderr)
        try:
            import signal
            signal.pthread_kill(self._main_ident, signal.SIGINT)
        except (AttributeError, ValueError, OSError, TypeError):
            import _thread
            _thread.interrupt_main()

    def close(self) -> None:
        self._closed.set()


# ---- postmortem rendering / diagnosis -------------------------------------

def load_postmortem(path: str) -> Dict[str, Any]:
    with open(path) as f:
        pm = json.load(f)
    if not isinstance(pm, dict) or "reason" not in pm:
        raise ValueError(f"{path}: not a ponyc_tpu postmortem "
                         "(no 'reason' field)")
    return pm


def _fmt_flags(flags: Dict[str, Any]) -> str:
    up = [k for k, v in (flags or {}).items() if v]
    return ",".join(up) if up else "-"


def render_postmortem(pm: Dict[str, Any]) -> str:
    """Human text of a postmortem dict — what dump() prints to stderr
    and `doctor --postmortem` shows under its one-line verdict."""
    lines = ["=== ponyc_tpu flight-recorder postmortem ==="]
    lines.append(f"reason: {pm.get('reason', '?')}")
    ph = pm.get("phase") or {}
    lines.append(f"phase: {ph.get('name', '?')} "
                 f"(age {ph.get('age_s', '?')}s, "
                 f"epoch {ph.get('epoch', '?')})  "
                 f"steps_run={pm.get('steps_run', '?')}  "
                 f"pid={pm.get('pid', '?')}")
    q = pm.get("queues") or {}
    if q:
        lines.append(f"queues: inject={q.get('inject', 0)} "
                     f"fast={q.get('fast', 0)}")
    errs = pm.get("errors") or []
    for e in errs:
        lines.append(f"error: {e['class']} (code {e['code']}) "
                     f"x{e['count']}")
    ck = pm.get("checkpoint")
    if ck and ck.get("path"):
        lines.append(
            f"restorable from: {ck['path']} (age {ck.get('age_s', '?')}s,"
            f" seq {ck.get('seq', '?')}, checksum "
            f"{'ok' if ck.get('verified') else 'unverified'})")
    elif ck is not None:
        lines.append("restorable from: (no checkpoint written yet)")
    ctrl = pm.get("controller")
    if ctrl:
        lines.append(f"controller: window={ctrl.get('window')} "
                     f"state={ctrl.get('state')} "
                     f"grows={ctrl.get('grows')} "
                     f"shrinks={ctrl.get('shrinks')}")
    wins = pm.get("windows") or []
    if wins:
        lines.append(f"last {len(wins)} windows (newest last):")
        for w in wins[-8:]:
            # wall/wait/since_prev: absent before ISSUE 24's records
            clock = ("" if "wall_ms" not in w else
                     f"wall={w['wall_ms']}ms wait={w.get('wait_ms')}ms "
                     f"since_prev={w.get('since_prev_ms')}ms ")
            # dispatch/outside: absent before ISSUE 35's
            if "dispatch_ms" in w:
                clock += f"dispatch={w['dispatch_ms']}ms "
            if w.get("outside_ms"):
                clock += "outside=" + ",".join(
                    f"{p}:{ms}ms" for p, ms in w["outside_ms"].items()) + " "
            lines.append(
                f"  step={w['step']} ticks={w['ticks']}/{w['budget']} "
                f"gap={w['gap_us']}us {clock}occ={w['occ_sum']} "
                f"qw_p99={w['qw_p99']} flags={_fmt_flags(w['flags'])}")
    srv = pm.get("serving")
    if srv:
        sh = srv.get("shed") or {}
        lines.append(
            f"serving: frames={srv.get('frames')} "
            f"accepted={srv.get('accepted')} "
            f"replied={srv.get('replied')} "
            f"shed={srv.get('shed_total')} "
            f"(rate {srv.get('shed_rate')}; "
            + ", ".join(f"{k}={v}" for k, v in sorted(sh.items()))
            + f") queue={srv.get('queue')} "
            f"inflight={srv.get('inflight')} "
            f"admit_limit={(srv.get('admission') or {}).get('limit')} "
            f"net_pending={srv.get('net_pending_bytes')}B"
            + (" DRAINING" if srv.get("draining") else ""))
    mail = pm.get("host_mail") or []
    if mail:
        lines.append("recent host mail: " + ", ".join(
            f"a{m['actor']}.{m['behaviour']}" for m in mail[-6:]))
    # The compiler's cost record (costs.capture) — absent unless the
    # run captured it: .get() everything, render nothing rather than
    # crash the crash report.
    meas = pm.get("measured") or {}
    for exe, rec in sorted((meas.get("executables") or {}).items()):
        if not isinstance(rec, dict) or rec.get("error"):
            continue
        bits = []
        if rec.get("flops") is not None:
            bits.append(f"flops={rec['flops']:.3g}")
        if rec.get("bytes_accessed") is not None:
            bits.append(f"bytes={rec['bytes_accessed']:.3g}")
        if rec.get("peak_bytes") is not None:
            bits.append(f"peak={rec['peak_bytes']}B")
        if bits:
            lines.append(f"measured [{exe}] "
                         f"({meas.get('backend', '?')}): "
                         + " ".join(bits))
    env = pm.get("env") or {}
    if env:
        lines.append(f"env: libtpu_importable="
                     f"{env.get('libtpu_importable')} "
                     + " ".join(f"{k}={v}" for k, v in
                                sorted((env.get('env') or {}).items())))
    return "\n".join(lines)


def diagnose_postmortem(pm: Dict[str, Any]) -> Tuple[str, str]:
    """(one_line_verdict, detail_text) for a postmortem — the doctor's
    reading; the one-liner is what the CLI leads with."""
    reason = str(pm.get("reason", "?"))
    ph = pm.get("phase") or {}
    wins = pm.get("windows") or []
    last = wins[-1] if wins else None
    if reason.startswith("watchdog"):
        hint = ""
        if ph.get("name") == "in-flight":
            hint = " (device never retired the window: backend hang " \
                   "or a runaway in-window loop)"
        elif ph.get("name") == "host-work":
            hint = " (a host behaviour, poller or GC pass is stuck)"
        elif ph.get("name") == "backend-init":
            hint = " (jax backend init never returned: is another " \
                   "process holding the chip?)"
        line = (f"STALLED: {reason}{hint}")
    elif (pm.get("errors") or []):
        e = pm["errors"][-1]
        line = (f"CRASHED: {e['class']} (code {e['code']}) at step "
                f"{pm.get('steps_run', '?')}")
        if last is not None and last["flags"].get("spill_overflow"):
            line += " — spill overflow: raise spill_cap/mailbox_cap " \
                    "or lower overload_threshold"
    elif reason.startswith(("SIGQUIT", "manual", "stop")):
        line = (f"SNAPSHOT: {reason} at step {pm.get('steps_run', '?')} "
                f"(phase {ph.get('name', '?')}) — no failure recorded")
    else:
        line = f"CRASHED: {reason} at step {pm.get('steps_run', '?')}"
    if last is not None and int(last.get("occ_max", 0)) > 0 \
            and "STALLED" in line:
        line += (f"; {last['occ_sum']} message(s) still queued "
                 f"(deepest {last['occ_max']})")
    srv = pm.get("serving")
    if srv and line.startswith(("STALLED", "CRASHED")):
        # Serving-aware verdict (ISSUE 9): was the front door shedding
        # (edge held) and how much reply backlog died with the world?
        line += (f"; serving: shed_rate={srv.get('shed_rate')} "
                 f"inflight={srv.get('inflight')} "
                 f"net_pending={srv.get('net_pending_bytes')}B")
    ck = pm.get("checkpoint")
    if ck and ck.get("path") and line.startswith(("STALLED", "CRASHED")):
        # The doctor's recovery pointer: what the supervisor would
        # restore from (`python -m ponyc_tpu supervise`, supervise.py).
        line += f" — restorable from {ck['path']}"
    return line, render_postmortem(pm)
