"""Adaptive run loop tests (PROFILE.md §9): the window controller's
decision rules, the on-device tick-0 gate of the pipelined dispatch
(engine.build_multi_step_gated), pipelined-vs-synchronous differential
equivalence (message-for-message, exit-code-equal), adaptive
convergence on the quiet ubench, quiesce_interval="auto" resolution
through the tuning cache, and interrupt safety of an in-flight
pipelined window (SIGINT/SIGTERM subprocess tests)."""

import os
import signal

import numpy as np
import pytest

import _child
from ponyc_tpu import (I32, Ref, Runtime, RuntimeOptions, actor,
                       behaviour)
from ponyc_tpu.runtime import engine
from ponyc_tpu.runtime.controller import WindowController

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The controller/gap tests must not read or publish converged windows.
NO_CACHE = dict(tuning_cache="off")


def _opts(**kw):
    base = dict(mailbox_cap=4, batch=1, max_sends=1, msg_words=1,
                spill_cap=256, inject_slots=8, **NO_CACHE)
    base.update(kw)
    return RuntimeOptions(**base)


# ------------------------------------------------ controller decisions

def test_controller_grows_geometrically_on_quiet_budget_exits():
    c = WindowController(8, 4, 128)
    seen = []
    for _ in range(6):
        seen.append(c.observe(ran=c.window, budget=c.window,
                              attention=False))
    assert seen == [16, 32, 64, 128, 128, 128]   # ×2 then clamped at hi
    assert c.state in ("grow", "steady")


def test_controller_shrinks_on_host_attention():
    c = WindowController(64, 4, 128)
    assert c.observe(ran=10, budget=64, attention=True) == 32
    assert c.state == "shrink"
    assert c.observe(ran=5, budget=32, attention=True) == 16
    for _ in range(10):
        c.observe(ran=1, budget=c.window, attention=True)
    assert c.window == 4                          # clamped at lo


def test_controller_shrinks_on_queue_wait_pressure():
    c = WindowController(64, 4, 128)
    # p99 queue wait longer than the whole window: latency pressure.
    assert c.observe(ran=64, budget=64, attention=False,
                     qw_p99=256) == 32
    assert c.state == "shrink"
    # At the floor, pressure cannot shrink further (and is not counted
    # as a shrink decision).
    c2 = WindowController(4, 4, 128)
    before = c2.shrinks
    nxt = c2.observe(ran=4, budget=4, attention=False, qw_p99=1024)
    assert nxt == 8 and c2.shrinks == before     # grew instead (quiet
    #                                              full-budget exit)


def test_controller_holds_on_early_quiescence():
    c = WindowController(32, 4, 128)
    assert c.observe(ran=7, budget=32, attention=False) == 32
    assert c.observe(ran=1, budget=32, attention=False) == 32
    assert c.holds == 2


def test_controller_reaches_steady_at_cap():
    c = WindowController(32, 4, 64)
    for _ in range(8):
        c.observe(ran=c.window, budget=c.window, attention=False)
    assert c.window == 64 and c.state == "steady"


def test_controller_fixed_mode_lo_eq_hi():
    c = WindowController(16, 16, 16)
    for att in (False, True, False):
        assert c.observe(ran=16, budget=16, attention=att) == 16
    assert c.window == 16


def test_controller_deterministic_from_recorded_trace():
    trace = [(64, 64, False, 0), (64, 64, False, 0), (10, 128, True, 0),
             (64, 64, False, 300), (3, 32, False, 0), (32, 32, False, 0)]
    def replay():
        c = WindowController(64, 4, 256)
        return [c.observe(r, b, att, qw) for r, b, att, qw in trace], \
            c.snapshot()
    d1, s1 = replay()
    d2, s2 = replay()
    assert d1 == d2 and s1 == s2                 # pure + deterministic


def test_controller_bounds_validated():
    with pytest.raises(ValueError):
        WindowController(8, 0, 4)
    with pytest.raises(ValueError):
        WindowController(8, 16, 4)
    with pytest.raises(ValueError):
        RuntimeOptions(quiesce_interval="sometimes")
    with pytest.raises(ValueError):
        RuntimeOptions(quiesce_interval_min=8, quiesce_interval_max=4)


# ------------------------------------------------ the on-device gate

@actor
class Node:
    acc: I32
    nxt: Ref["Node"]

    MAX_SENDS = 1

    @behaviour
    def step(self, st, v: I32):
        self.send(st["nxt"], Node.step, v - 1, when=v > 0)
        return {**st, "acc": st["acc"] + v}


def _ring(n=8, hops=100, **okw):
    rt = Runtime(_opts(**okw))
    rt.declare(Node, n)
    rt.start()
    ids = rt.spawn_many(Node, n)
    rt.set_fields(Node, ids, nxt=np.roll(ids, -1))
    rt.send(int(ids[0]), Node.step, hops)
    return rt, ids


def test_gate_closes_on_stale_attention_aux():
    """A window dispatched behind a 'host attention' aux must be an
    identity pass: zero ticks, aux passed through unchanged."""
    import jax
    import jax.numpy as jnp
    rt, _ids = _ring()
    inj = rt._drain_inject()
    # Real first window: runs (force himself is not even needed — the
    # inject makes zero_aux's device_pending=True gate pass).
    st, aux, k = rt._multi_g(rt.state, *inj, jnp.int32(4),
                             np.bool_(True), rt._zero_aux)
    rt.state = st
    assert int(k) == 4
    # Forge a stale attention vote: same aux but host_pending=True.
    stale = jax.device_get(aux)._replace(host_pending=np.bool_(True))
    st2, aux2, k2 = rt._multi_g(rt.state, *rt._empty_inject,
                                jnp.int32(8), np.bool_(False), stale)
    rt.state = st2
    assert int(k2) == 0                      # gated out entirely
    a2 = jax.device_get(aux2)
    assert bool(a2.host_pending)             # prev aux passed through
    assert int(a2.n_processed) == int(jax.device_get(aux).n_processed)


def test_gate_closes_on_stale_quiet_aux_keeps_quiescence_exact():
    import jax
    import jax.numpy as jnp
    rt, _ids = _ring(hops=2)
    rt.run(max_steps=100)                    # quiesce for real
    quiet = rt._zero_aux._replace(device_pending=np.bool_(False))
    st, aux, k = rt._multi_g(rt.state, *rt._empty_inject, jnp.int32(8),
                             np.bool_(False), quiet)
    rt.state = st
    # A stale "quiet" vote runs nothing — termination is only ever
    # declared from an aux no later tick has invalidated.
    assert int(k) == 0
    assert not bool(jax.device_get(aux).device_pending)


def test_gated_out_window_requeues_injections():
    """_retire_window puts a gated-out window's consumed injections
    back at the FRONT of the queue, order preserved."""
    rt, ids = _ring(hops=0)
    rt.run(max_steps=50)
    rt.send(int(ids[0]), Node.step, 5)
    rt.send(int(ids[1]), Node.step, 7)
    inj_t, inj_w, consumed = rt._drain_inject_tracked()
    assert len(consumed) == 2 and not rt._inject_q
    import jax.numpy as jnp
    quiet = rt._zero_aux._replace(device_pending=np.bool_(False))
    st, aux, k = rt._multi_g(rt.state, inj_t, inj_w, jnp.int32(4),
                             np.bool_(False), quiet)
    rt.state = st
    win = {"aux": aux, "k": k, "budget": 4, "consumed": consumed,
           "gap_ns": 0, "epoch": rt._state_epoch, "pipelined": True,
           "seq": rt._rl_seq + 1, "t_dispatch": 0.0}
    k2, _a = rt._retire_window(win)
    assert k2 == 0
    assert [t for t, _w in rt._inject_q] == [int(ids[0]), int(ids[1])]
    # And the loop delivers them on the next real run.
    assert rt.run(max_steps=100) == 0
    acc = np.asarray(rt.cohort_state(Node)["acc"])
    assert acc.sum() == sum(range(6)) + sum(range(8))


# ------------------------------------ pipelined vs synchronous oracle

@actor
class HostLog:
    HOST = True
    ends: I32
    total: I32

    @behaviour
    def done(self, st, tail: I32):
        return {**st, "ends": st["ends"] + 1, "total": st["total"] + tail}


@actor
class WalkerH:
    acc: I32
    nxt: Ref["WalkerH"]
    log: Ref["HostLog"]

    MAX_SENDS = 2

    @behaviour
    def step(self, st, v: I32):
        self.send(st["nxt"], WalkerH.step, v - 1, when=v > 0)
        self.send(st["log"], HostLog.done, st["acc"] + v, when=v == 0)
        return {**st, "acc": st["acc"] + v}


@actor
class Exiter:
    n: I32

    MAX_SENDS = 1

    @behaviour
    def count(self, st, v: I32):
        self.send(self.actor_id, Exiter.count, v - 1, when=v > 0)
        self.exit(code=42, when=v == 0)
        return {**st, "n": st["n"] + 1}


def _mode_opts(pipelined: bool, **kw):
    if pipelined:
        return _opts(pipeline=True, quiesce_interval="auto",
                     quiesce_interval_min=4, quiesce_interval_max=64,
                     **kw)
    return _opts(pipeline=False, quiesce_interval=16, **kw)


def _run_walker_world(seed: int, pipelined: bool):
    """Random functional-graph walk + device→host reporting: the same
    corpus shape as the fuzz differential (commutative outcomes, so any
    correct schedule must agree column-for-column)."""
    rng = np.random.default_rng(seed)
    n, chains = 12, 6
    rt = Runtime(_mode_opts(pipelined, mailbox_cap=4, max_sends=2,
                            msg_words=1))
    rt.declare(WalkerH, n).declare(HostLog, 1)
    rt.start()
    log = rt.spawn(HostLog, ends=0, total=0)
    ids = rt.spawn_many(WalkerH, n, log=log)
    rt.set_fields(WalkerH, ids, nxt=ids[rng.integers(0, n, n)])
    starts = rng.choice(n, chains, replace=False)
    vals = rng.integers(1, 40, chains)
    for s, v in zip(starts, vals):
        rt.send(int(ids[s]), WalkerH.step, int(v))
    code = rt.run(max_steps=200_000)
    st = rt.cohort_state(WalkerH)
    return {
        "code": code,
        "acc": np.asarray(st["acc"]).tolist(),
        "host": rt.state_of(log),
        "processed": rt.counter("n_processed"),
        "delivered": rt.counter("n_delivered"),
        "host_processed": rt.totals.get("host_processed", 0),
    }


@pytest.mark.parametrize("seed", [7, 23, 91])
def test_differential_pipelined_matches_synchronous(seed):
    """The tentpole oracle: the pipelined adaptive loop and the forced
    synchronous fixed-window loop agree message-for-message (equal
    processed/delivered totals, equal per-actor columns, equal host
    actor state) and exit-code-equal on the fuzz corpus shape."""
    sync = _run_walker_world(seed, pipelined=False)
    pipe = _run_walker_world(seed, pipelined=True)
    assert sync == pipe


def test_differential_fifo_order_under_pipelined_loop():
    """Per-edge FIFO (the order-sensitive oracle of test_fifo) holds
    under the pipelined adaptive loop: reuse that suite's harness with
    pipelining forced on and the window adaptive."""
    from test_fifo import run_fifo
    run_fifo(seed=101, okw=dict(
        mailbox_cap=2, batch=1, max_sends=3, spill_cap=2048,
        inject_slots=16, pipeline=True, quiesce_interval="auto",
        quiesce_interval_min=4, quiesce_interval_max=64, **NO_CACHE))


def test_differential_exit_code_equal():
    for pipelined in (False, True):
        rt = Runtime(_mode_opts(pipelined))
        rt.declare(Exiter, 1)
        rt.start()
        eid = rt.spawn(Exiter, n=0)
        rt.send(eid, Exiter.count, 30)
        assert rt.run(max_steps=10_000) == 42
        assert int(rt.state_of(eid)["n"]) == 31


# ----------------------------------------- adaptive loop integration

def test_adaptive_converges_to_steady_on_quiet_ubench():
    """Acceptance: on the never-quiescing, zero-host-attention ubench
    the controller grows geometrically to its cap and reports steady."""
    from ponyc_tpu.models import ubench
    opts = RuntimeOptions(
        mailbox_cap=4, batch=1, max_sends=1, msg_words=1,
        spill_cap=256, inject_slots=8, pipeline=True,
        quiesce_interval="auto", quiesce_interval_min=4,
        quiesce_interval_max=256, **NO_CACHE)
    rt, ids = ubench.build(64, opts)
    ubench.seed_all(rt, ids, hops=1 << 30)
    rt.run(max_steps=1600)
    rl = rt.run_loop_stats()
    c = rl["controller"]
    assert c["state"] == "steady" and c["window"] == 256, rl
    assert c["grows"] >= 2                       # geometric ascent ran
    assert rl["pipelined_dispatches"] > 0        # the bridge pipelined
    assert rl["windows"] >= 8
    assert rt.steps_run == 1600                  # max_steps exact


def test_run_loop_stats_host_gap_accounting():
    rt, _ids = _ring(hops=400, pipeline=False, quiesce_interval=8)
    assert rt.run(max_steps=2_000) == 0
    rl = rt.run_loop_stats()
    assert rl["pipelined_dispatches"] == 0       # sync mode never rides
    assert rl["windows"] > 1
    assert rl["host_gap_us_total"] >= 0
    assert rl["controller"]["window"] == 8       # fixed mode holds


def test_quiesce_auto_resolves_and_persists_through_tuning_cache(
        tmp_path, monkeypatch):
    from ponyc_tpu import tuning
    monkeypatch.setenv("PONY_TPU_TUNING_CACHE", str(tmp_path))
    from ponyc_tpu.models import ubench
    opts = RuntimeOptions(
        mailbox_cap=4, batch=1, max_sends=1, msg_words=1,
        spill_cap=256, inject_slots=8, quiesce_interval="auto",
        quiesce_interval_min=4, quiesce_interval_max=128)
    rt, ids = ubench.build(64, opts)
    assert rt.opts.quiesce_interval == tuning.DEFAULT_QUIESCE_INTERVAL
    assert rt.tuning_record["quiesce_interval"]["source"] == "default"
    ubench.seed_all(rt, ids, hops=1 << 30)
    rt.run(max_steps=1024)                       # grows 64→128, steady
    assert rt._controller.state == "steady"
    assert rt._controller.window == 128
    # Second start of the same layout resolves to the CONVERGED window.
    rt2, _ids2 = ubench.build(64, opts)
    rec = rt2.tuning_record["quiesce_interval"]
    assert rec["source"] == "cache" and rec["initial"] == 128, rec
    assert rt2.opts.quiesce_interval == 128


def test_qw_p99_aux_lane():
    """The queue-wait p99 rides the aux at analysis>=1 (the controller's
    pressure signal) and stays a folded zero at level 0."""
    import jax
    import jax.numpy as jnp
    for level, expect_pos in ((1, True), (0, False)):
        rt, ids = _ring(hops=20, analysis=level, mailbox_cap=8)
        st, aux, _k = rt._multi_g(rt.state, *rt._drain_inject(),
                                  jnp.int32(8), jnp.bool_(True),
                                  rt._zero_aux)
        rt.state = st
        a = jax.device_get(aux)
        if expect_pos:
            assert int(a.qw_p99) >= 1, a.qw_p99
        else:
            assert int(a.qw_p99) == 0


# ------------------------------------------------- interrupt safety

def test_keyboard_interrupt_mid_pipeline_is_clean(tmp_path):
    """SIGINT while pipelined windows are in flight: run() must sync the
    in-flight window, keep host-outbox messages, and leave the runtime
    restartable (no donated-buffer reuse)."""
    code = f"""
import os, signal, sys, threading
sys.path.insert(0, {ROOT!r})
from ponyc_tpu.platforms import force_cpu
force_cpu()
import numpy as np
from ponyc_tpu import I32, Ref, RuntimeOptions, Runtime, actor, behaviour

@actor
class Pinger:
    nxt: Ref["Pinger"]
    MAX_SENDS = 1
    @behaviour
    def ping(self, st, v: I32):
        self.send(st["nxt"], Pinger.ping, v, when=True)
        return st

@actor
class Sink:
    HOST = True
    got: I32
    @behaviour
    def hit(self, st, v: I32):
        return {{**st, "got": st["got"] + 1}}

rt = Runtime(RuntimeOptions(mailbox_cap=4, batch=1, max_sends=1,
                            msg_words=1, inject_slots=8,
                            quiesce_interval="auto", tuning_cache="off"))
rt.declare(Pinger, 16).declare(Sink, 1)
rt.start()
sink = rt.spawn(Sink, got=0)
ids = rt.spawn_many(Pinger, 16)
rt.set_fields(Pinger, ids, nxt=np.roll(ids, -1))
for i in ids:                       # endless device traffic
    rt.send(int(i), Pinger.ping, 1)
rt.send(sink, Sink.hit, 7)          # one host-outbox message in flight
threading.Timer(1.0, lambda: os.kill(os.getpid(), signal.SIGINT)).start()
try:
    rt.run()                        # runs until the SIGINT
    print("NO-INTERRUPT")
except KeyboardInterrupt:
    # Clean stop: state consistent, host message delivered, restart OK.
    rt.check_invariants()
    assert rt.state_of(sink)["got"] == 1, rt.state_of(sink)
    rt.run(max_steps=32)            # donated buffers must still be live
    rt.check_invariants()
    print("INTERRUPT-CLEAN got", rt.state_of(sink)["got"],
          "steps", rt.steps_run)
"""
    p = _child.script(code, env={"PONY_TPU_TUNING_CACHE": "off"})
    assert p.returncode == 0, (p.returncode, p.stdout, p.stderr)
    assert "INTERRUPT-CLEAN got 1" in p.stdout, (p.stdout, p.stderr)
    assert "NO-INTERRUPT" not in p.stdout


def test_sigterm_mid_pipeline_dumps_and_terminates(tmp_path):
    """SIGTERM during an in-flight pipelined window (analysis=1): the
    dump handler must observe a consistent world (the dispatch critical
    section defers delivery) and the process still dies of SIGTERM —
    alongside test_profiler's quiescent-world SIGTERM test."""
    code = f"""
import os, signal, sys, threading
sys.path.insert(0, {ROOT!r})
from ponyc_tpu.platforms import force_cpu
force_cpu()
import numpy as np
from ponyc_tpu import I32, Ref, RuntimeOptions, Runtime, actor, behaviour

@actor
class Pinger:
    nxt: Ref["Pinger"]
    MAX_SENDS = 1
    @behaviour
    def ping(self, st, v: I32):
        self.send(st["nxt"], Pinger.ping, v, when=True)
        return st

rt = Runtime(RuntimeOptions(mailbox_cap=4, batch=1, max_sends=1,
                            msg_words=1, inject_slots=8, analysis=1,
                            quiesce_interval="auto", tuning_cache="off"))
rt.declare(Pinger, 16)
rt.start()
ids = rt.spawn_many(Pinger, 16)
rt.set_fields(Pinger, ids, nxt=np.roll(ids, -1))
for i in ids:
    rt.send(int(i), Pinger.ping, 1)

class Killer:
    # Not after a fixed time (on a loaded machine that lands before
    # run() has compiled and installed the dump handler): once the run
    # loop has retired a window and holds another in flight behind it.
    fired = False
    def poll(self, rt):
        s = rt.run_loop_stats()
        in_flight = s["sync_dispatches"] + s["pipelined_dispatches"] \
            - s["windows"]
        if (not self.fired and s["windows"] >= 1 and in_flight >= 1
                and s["pipelined_dispatches"] >= 1):
            self.fired = True
            os.kill(os.getpid(), signal.SIGTERM)

rt.register_poller(Killer())
rt.run()
print("SURVIVED-SIGTERM")
"""
    p = _child.script(code, env={"PONY_TPU_TUNING_CACHE": "off"})
    assert p.returncode == -signal.SIGTERM, (p.returncode, p.stderr)
    assert "ponyc_tpu analysis dump" in p.stderr, p.stderr
    assert "run_loop window=" in p.stderr, p.stderr
    assert "SURVIVED-SIGTERM" not in p.stdout
    assert "Traceback" not in p.stderr, p.stderr


def test_window_constants_ride_optimization_barrier():
    """Compile-time regression guard (PR 11 satellite): the gated
    window's loop-invariant operands (injections, limit, force bit)
    must sit behind lax.optimization_barrier in the lowered HLO.
    Without it XLA constant-folds them INTO the while body and a
    constant-propagation sweep re-runs per window compile — the stall
    an early bench run's tail recorded. The barrier's presence
    in the StableHLO text is the cheapest stable proxy for "the hoist
    survived lowering"."""
    import jax
    import jax.numpy as jnp
    from ponyc_tpu.models import ubench
    opts = RuntimeOptions(mailbox_cap=4, batch=1, max_sends=1,
                          msg_words=1, spill_cap=64, inject_slots=8,
                          **NO_CACHE)
    rt, _ids = ubench.build(8, opts)
    gated = engine.build_multi_step_gated(rt.program, rt.opts)
    text = jax.jit(gated).lower(
        rt.state, *rt._empty_inject, jnp.int32(4), jnp.bool_(True),
        rt._zero_aux).as_text()
    assert "optimization_barrier" in text


# ------------------------------------------- run-loop phases (ISSUE 24)

class _Recording:
    """Stands in for jax.profiler.TraceAnnotation: a profiler session
    that keeps (kind, name, depth, meta) in `log`."""
    depth = 0
    log: list = []

    def __init__(self, name, **meta):
        self.name, self.meta = name, meta

    @staticmethod
    def is_enabled():
        return True

    def __enter__(self):
        _Recording.log.append(("enter", self.name, _Recording.depth,
                               self.meta))
        _Recording.depth += 1

    def __exit__(self, *_exc):
        _Recording.depth -= 1
        _Recording.log.append(("exit", self.name, _Recording.depth,
                               self.meta))


@pytest.fixture
def recording(monkeypatch):
    from ponyc_tpu.runtime import runtime as rtmod
    _Recording.depth, _Recording.log = 0, []
    monkeypatch.setattr(rtmod, "TraceAnnotation", _Recording)
    return _Recording.log


def test_run_phases_nest_carry_window_and_cover_run(monkeypatch):
    """The pony:* spans of one run() are properly nested, the window's
    spans carry its sequence number, and the per-phase seconds (self
    time) add up to run()'s wall clock."""
    import time

    from ponyc_tpu.runtime import runtime as rtmod

    log = _Recording.log = []
    _Recording.depth = 0

    rt, _ids = _ring(hops=3000, quiesce_interval=64)
    rt.run(max_steps=64)                        # compile outside the clock
    before = rt.run_loop_stats()
    monkeypatch.setattr(rtmod, "TraceAnnotation", _Recording)
    t0 = time.perf_counter()
    assert rt.run(max_steps=100_000) == 0
    wall = time.perf_counter() - t0
    monkeypatch.undo()
    after = rt.run_loop_stats()
    rt.stop()

    # properly nested: every exit closes the newest open span
    stack = []
    for kind, name, depth, _meta in log:
        if kind == "enter":
            assert depth == len(stack)
            stack.append(name)
        else:
            assert stack.pop() == name and depth == len(stack)
    assert not stack
    names = {name for _k, name, _d, _m in log}
    assert {"pony:enter", "pony:dispatching", "pony:wait",
            "pony:host-work", "pony:exit"} <= names
    assert names <= {"pony:" + p for p in rtmod.RUN_PHASES}
    # top level covers run() from its first line to its last
    assert log[0][1] == "pony:enter" and log[-1][1] == "pony:exit"
    # a window's spans share its sequence number; host-work knows ticks
    windows = after["windows"] - before["windows"]
    assert windows > 3
    for phase in ("pony:dispatching", "pony:wait", "pony:host-work"):
        seqs = {m["window"] for k, n, _d, m in log
                if k == "enter" and n == phase}
        assert len(seqs) >= windows, (phase, seqs)
    assert any(m.get("ticks") for _k, n, _d, m in log
               if n == "pony:host-work")
    spent = sum(after["phase_s"][p] - before["phase_s"][p]
                for p in rtmod.RUN_PHASES)
    # Three readings of one clock: the spans lie inside run(), and the
    # windows' walls tile [first dispatch, last retire], inside run()
    # too. What run() does BETWEEN two spans (a few lines of the loop
    # and the spans' own book-keeping, ~0.2 ms a window: 3% of this run
    # on an idle machine) is in the windows' walls and in `wall` but in
    # no phase, and it stretches when busy test workers deschedule the
    # interpreter there. So `spent` is held from above exactly, and
    # from below to most of the windows' walls, not to a few percent of
    # a second clock.
    windows_wall = after["windows_wall_s"] - before["windows_wall_s"]
    assert 0 < windows_wall <= wall
    assert 0.8 * windows_wall <= spent <= wall


def test_run_phases_cost_little_with_the_profiler_off():
    """Budget: about a microsecond a span with no profiler session
    (generous bound: CI machines are slow and shared), for a phase of
    the run loop and for a public call made a phase by `_api_phase`
    (its meta is not even evaluated without a session)."""
    import time

    from ponyc_tpu.runtime import runtime as rtmod
    rt, _ids = _ring(hops=10)
    n = 20_000
    t0 = time.perf_counter()
    for i in range(n):
        with rt._phase("host-work", window=i):
            pass
    per_span = (time.perf_counter() - t0) / n
    assert per_span < 20e-6, per_span
    assert not rt._phase_stack
    assert rtmod.PHASE_STAMPS["wait"] == "in-flight"   # the watchdog's word

    def meta(_self, _i):
        raise AssertionError("meta evaluated with no profiler session")
    call = rtmod._api_phase("counter", meta)(lambda _self, i: i)
    before = rt.run_loop_stats()
    t0 = time.perf_counter()
    for i in range(n):
        call(rt, i)
    per_call = (time.perf_counter() - t0) / n
    after = rt.run_loop_stats()
    rt.stop()
    assert per_call < 20e-6, per_call
    assert after["phase_n"]["counter"] - before["phase_n"]["counter"] == n
    assert after["phase_s"]["counter"] > before["phase_s"]["counter"]
    assert not rt._phase_stack


# ------------------------- the host's side outside run() (ISSUE 35)

def test_every_phase_the_program_emits_is_declared():
    """RUN_PHASES is the vocabulary: every name handed to `_phase`,
    `_api_phase` or a `_PhaseSpan` anywhere in the package is in it, and
    every API phase is used."""
    import re

    from ponyc_tpu.runtime import runtime as rtmod
    emitted = set()
    pkg = os.path.join(ROOT, "ponyc_tpu")
    for d, _dirs, files in os.walk(pkg):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    emitted |= set(re.findall(
                        r"(?:_phase|_PhaseSpan)\(\s*(?:self,\s*)?"
                        r"\"([a-z\-]+)\"", fh.read()))
    assert emitted, "the scan found no phase"
    assert emitted <= set(rtmod.RUN_PHASES), emitted - set(rtmod.RUN_PHASES)
    assert set(rtmod.API_PHASES) <= emitted
    assert len(set(rtmod.RUN_PHASES)) == len(rtmod.RUN_PHASES)
    # the leaves benchmarks/phase_trace.py sums by name are no API phase
    assert not set(rtmod.API_PHASES) & {
        "dispatching", "host-work", "outbox", "pollers", "gc",
        "checkpoint", "analysis"}


def _api_tour(rt_opts):
    """Every public call that is an API phase, once each but `read`
    (twice); returns the runtime, stopped."""
    rt = Runtime(rt_opts)
    rt.declare(Node, 8)
    rt.start()
    ids = rt.spawn_many(Node, 8)
    rt.set_fields(Node, ids, nxt=np.roll(ids, -1))
    rt.bulk_send(ids[:3], Node.step, np.array([5, 5, 5]))
    handles = rt.blob_store_many(4, fill=lambda k, w: k + w)
    assert rt.run() == 0
    assert rt.blob_fetch_many(handles[:2]).shape == (2, 16)
    assert rt.cohort_state(Node)["nxt"].shape == (8,)
    assert rt.state_of(int(ids[0]))["nxt"] == int(ids[1])
    assert rt.counter("n_processed") == 18
    rt.stop()
    return rt


API_CALLS = {"start": 1, "spawn": 1, "set-fields": 1, "bulk-send": 1,
             "blob-store": 1, "blob-fetch": 1, "read": 2, "counter": 1,
             "stop": 1}


def test_api_phases_are_spans_with_their_meta_under_a_session(recording):
    """Outside run() every public call that touches the device is a
    `pony:` span, at depth 0, with what it moved as meta."""
    from ponyc_tpu.runtime import runtime as rtmod
    assert set(API_CALLS) == set(rtmod.API_PHASES)
    rt = _api_tour(_opts(blob_slots=8, blob_words=16))
    api = [(name, meta) for kind, name, depth, meta in recording
           if kind == "enter" and name[5:] in API_CALLS]
    assert all(depth == 0 for kind, name, depth, _m in recording
               if kind == "enter" and name[5:] in API_CALLS)
    assert [name for name, _m in api] == [
        "pony:start", "pony:spawn", "pony:set-fields", "pony:bulk-send",
        "pony:blob-store", "pony:blob-fetch", "pony:read", "pony:read",
        "pony:counter", "pony:stop"]
    meta = dict((name, m) for name, m in api if name != "pony:read")
    assert meta["pony:spawn"] == {"count": 8}
    assert meta["pony:set-fields"] == {"count": 8}
    assert meta["pony:bulk-send"] == {"count": 3}
    assert meta["pony:blob-store"] == {"blobs": 4}
    assert meta["pony:blob-fetch"] == {"blobs": 2}
    assert meta["pony:start"] == {"shards": 1}
    assert meta["pony:counter"] == meta["pony:stop"] == {}
    reads = [m for name, m in api if name == "pony:read"]
    assert reads == [{"words": 16}, {"words": 2}]    # 8 x (nxt, seen); one row
    assert rt.run_loop_stats()["phase_n"]["read"] == 2


def test_api_phases_are_counted_with_no_session():
    """Always on: seconds and calls in run_loop_stats(), no profiler."""
    rt = _api_tour(_opts(blob_slots=8, blob_words=16))
    rl = rt.run_loop_stats()
    for phase, calls in API_CALLS.items():
        assert rl["phase_n"][phase] == calls, phase
        assert rl["phase_s"][phase] > 0, phase
    assert rl["phase_n"]["dispatching"] == rl["phase_n"]["wait"] \
        == rl["sync_dispatches"] + rl["pipelined_dispatches"]
    assert set(rl["phase_n"]) == set(rl["phase_s"])


def test_dispatching_is_a_leaf_and_only_the_first_launch_is_cold(recording):
    """`pony:dispatching` has no child (benchmarks/phase_trace.py sums
    it by name), the first launch after start() carries cold=1 and no
    later one does — in a second run() either — and its seconds are
    counted apart; every launch says over how many shards."""
    rt, ids = _ring(hops=300, quiesce_interval=16)
    assert rt.run() == 0
    rt.send(int(ids[0]), Node.step, 40)
    assert rt.run() == 0
    rl = rt.run_loop_stats()
    rt.stop()
    launches = []
    for i, (kind, name, _depth, meta) in enumerate(recording):
        if kind == "enter" and name == "pony:dispatching":
            assert recording[i + 1][:2] == ("exit", "pony:dispatching")
            launches.append(meta)
    assert len(launches) > 4
    assert launches[0] == {"window": 1, "shards": 1, "cold": 1}
    assert all(set(m) == {"window", "shards"} for m in launches[1:])
    assert rl["cold_dispatches"] == 1
    assert 0 < rl["cold_dispatch_s"] <= rl["phase_s"]["dispatching"]
    # a second world's first launch is cold again: the flag is the
    # runtime's own, set by start()
    other, _ids = _ring(hops=5)
    assert other._cold_window
    assert other.run() == 0
    assert other.run_loop_stats()["cold_dispatches"] == 1
    other.stop()


def test_an_api_call_from_a_host_behaviour_nests_under_outbox(recording):
    """Inside run() such a span is a child like any other: under
    `pony:outbox`, never under `pony:dispatching`."""
    @actor
    class Poller:
        HOST = True
        seen: I32

        @behaviour
        def poll(self, st, _v: I32):
            return {**st, "seen": self.rt.counter("n_processed")}

    rt = Runtime(_opts())
    rt.declare(Node, 4).declare(Poller, 1).start()
    ids = rt.spawn_many(Node, 4)
    rt.set_fields(Node, ids, nxt=np.roll(ids, -1))
    p = rt.spawn(Poller)
    rt.send(int(ids[0]), Node.step, 6)
    rt.send(p, Poller.poll, 0)
    assert rt.run() == 0
    rt.stop()
    stack, parents = [], []
    for kind, name, _depth, _meta in recording:
        if kind == "enter":
            if name == "pony:counter":
                parents.append(tuple(stack))
            stack.append(name)
        else:
            stack.pop()
    assert parents and all(p[-1] == "pony:outbox" for p in parents), parents
