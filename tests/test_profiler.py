"""Per-behaviour profiler tests (≙ the fork's per-actor --ponyanalysis
records, analysis.h:16-31): the on-device telemetry matrix
(lanes.profile_lanes), queue-wait latency histograms, GC window stats,
Runtime.profile(), the window CSV's dynamic columns, per-behaviour
chrome-trace tracks, the `top` view, and the zero-cost-at-level-0
guarantee."""

import json
import os
import signal

import numpy as np
import pytest

import _child
from _hlo import bare_hlo
from _rebuild import block_indices, tick_indices
from ponyc_tpu import (I32, Ref, Runtime, RuntimeOptions, actor,
                       analysis, behaviour)
from ponyc_tpu.models import ring

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _opts(**kw):
    base = dict(mailbox_cap=8, batch=1, max_sends=1, msg_words=1,
                spill_cap=64, inject_slots=8)
    base.update(kw)
    return RuntimeOptions(**base)


# ---------------------------------------------------------------- matrix

@actor
class Worker:
    done: I32

    @behaviour
    def work(self, st, v: I32):
        return {**st, "done": st["done"] + v}

    @behaviour
    def reset(self, st, v: I32):
        return {**st, "done": v}


@actor
class Driver:
    out: Ref[Worker]
    left: I32
    MAX_SENDS = 2

    @behaviour
    def tick(self, st, _: I32):
        self.send(st["out"], Worker.work, 1, when=st["left"] > 0)
        self.send(self.actor_id, Driver.tick, 0, when=st["left"] > 1)
        return {**st, "left": st["left"] - 1}


def test_profile_sums_to_mesh_totals():
    """Acceptance: per-(cohort, behaviour) runs/deliveries and the
    queue-wait histograms sum to the mesh-wide n_processed/n_delivered
    on a multi-behaviour, multi-cohort example."""
    rt = Runtime(_opts(max_sends=2, msg_words=1, analysis=1,
                       spill_cap=256, inject_slots=32))
    rt.declare(Driver, 4).declare(Worker, 2).start()
    ws = rt.spawn_many(Worker, 2)
    ds = rt.spawn_many(Driver, 4, out=int(ws[0]), left=10)
    rt.set_fields(Driver, ds[2:], out=int(ws[1]))
    for w in ws:
        rt.send(int(w), Worker.reset, 0)
    for d in ds:
        rt.send(int(d), Driver.tick, 0)
    assert rt.run(max_steps=5000) == 0
    prof = rt.profile()
    beh = prof["behaviours"]
    assert set(beh) == {"Worker.work", "Worker.reset", "Driver.tick"}
    assert beh["Driver.tick"]["runs"] == 4 * 10
    assert beh["Worker.work"]["runs"] == 4 * 10
    assert beh["Worker.reset"]["runs"] == 2
    assert sum(b["runs"] for b in beh.values()) \
        == prof["totals"]["processed"] == rt.counter("n_processed")
    assert sum(b["delivered"] for b in beh.values()) \
        == prof["totals"]["delivered"] == rt.counter("n_delivered")
    hist_total = sum(sum(c["queue_wait_hist"])
                     for c in prof["cohorts"].values())
    assert hist_total == prof["totals"]["processed"]
    assert set(prof["cohorts"]) == {"Driver", "Worker"}


def test_queue_wait_single_token_ring():
    """A single-token ring dispatches every message exactly one tick
    after delivery: the whole histogram lands in bucket 0 (wait 1)."""
    rt, ids = ring.build(8, _opts(analysis=1))
    rt.send(int(ids[0]), ring.RingNode.token, 50)
    rt.run()
    c = rt.profile()["cohorts"]["RingNode"]
    assert c["queue_wait_hist"][0] == 50
    assert sum(c["queue_wait_hist"][1:]) == 0
    assert c["queue_wait_p50"] == 1 and c["queue_wait_p99"] == 1


def test_backpressure_attribution():
    """A flooded slow consumer shows up in the matrix: rejects blame
    the flooded behaviour, mute-ticks blame the muted senders' cohort,
    and the consumer's queue-wait spreads past bucket 0."""

    @actor
    class SlowP:
        n: I32
        BATCH = 1

        @behaviour
        def eat(self, st, v: I32):
            return {**st, "n": st["n"] + 1}

    @actor
    class FastP:
        out: Ref[SlowP]
        left: I32
        MAX_SENDS = 2

        @behaviour
        def go(self, st, _: I32):
            self.send(st["out"], SlowP.eat, 1, when=st["left"] > 0)
            self.send(self.actor_id, FastP.go, 0, when=st["left"] > 1)
            return {**st, "left": st["left"] - 1}

    rt = Runtime(RuntimeOptions(mailbox_cap=2, batch=1, msg_words=1,
                                max_sends=2, spill_cap=512,
                                inject_slots=16, analysis=1))
    rt.declare(FastP, 12).declare(SlowP, 1).start()
    s = rt.spawn(SlowP)
    fs = rt.spawn_many(FastP, 12, out=s, left=30)
    rt.bulk_send(fs, FastP.go, np.zeros(12, np.int64))
    assert rt.run(max_steps=30_000) == 0
    prof = rt.profile()
    assert prof["behaviours"]["SlowP.eat"]["rejected"] > 0
    assert prof["behaviours"]["FastP.go"]["rejected"] == 0
    assert prof["cohorts"]["FastP"]["mute_ticks"] > 0
    slow = prof["cohorts"]["SlowP"]
    assert sum(slow["queue_wait_hist"][1:]) > 0, \
        "a flooded mailbox must show waits > 1 tick"
    assert slow["queue_wait_p99"] >= slow["queue_wait_p50"]
    # rejected attribution matches the per-tick mesh counter semantics
    assert sum(b["rejected"] for b in prof["behaviours"].values()) \
        == rt.counter("n_rejected")


def test_host_behaviour_runs_counted():
    """Host-cohort behaviours dispatch host-side; profile() merges the
    host dispatch counts into the same matrix."""

    @actor
    class DevSrc:
        out: Ref
        MAX_SENDS = 1

        @behaviour
        def emit(self, st, v: I32):
            self.send(st["out"], HostSink.take, v)
            return st

    @actor
    class HostSink:
        HOST = True
        seen: I32

        @behaviour
        def take(self, st, v: I32):
            return {**st, "seen": st["seen"] + v}

    rt = Runtime(_opts(msg_words=2, analysis=1))
    rt.declare(DevSrc, 2).declare(HostSink, 1).start()
    sink = rt.spawn(HostSink)
    srcs = rt.spawn_many(DevSrc, 2, out=sink)
    for s in srcs:
        rt.send(int(s), DevSrc.emit, 3)
    rt.run()
    prof = rt.profile()
    assert prof["behaviours"]["HostSink.take"]["runs"] == 2
    assert prof["behaviours"]["DevSrc.emit"]["runs"] == 2
    assert rt.state_of(sink)["seen"] == 6


# -------------------------------------------------- zero-cost at level 0

def test_level0_state_carries_no_lanes():
    rt, _ = ring.build(8, _opts(analysis=0))
    assert rt.state.beh_runs.size == 0
    assert rt.state.beh_delivered.size == 0
    assert rt.state.beh_rejected.size == 0
    assert rt.state.coh_mute_ticks.size == 0
    assert rt.state.qwait_hist.size == 0
    assert rt.state.qwait_enq == {}
    with pytest.raises(RuntimeError, match="analysis >= 1"):
        rt.profile()


def test_level0_lanes_compile_to_baseline(monkeypatch):
    """Acceptance: at analysis=0 the step's jaxpr is IDENTICAL to a
    baseline built with the profiler lanes physically unreachable
    (profile_lanes trapped), proving level 0 traces zero telemetry ops;
    at analysis>=1 the same trap fires, proving the helper is the only
    source of the lanes."""
    import jax
    import jax.numpy as jnp

    from ponyc_tpu.program import Program
    from ponyc_tpu.runtime import engine, lanes
    from ponyc_tpu.runtime.state import init_state

    def build(analysis):
        opts = _opts(analysis=analysis, spill_cap=16, inject_slots=4)
        prog = Program(opts)
        prog.declare(ring.RingNode, 8)
        prog.finalize()
        st = init_state(prog, opts)
        step = engine.build_step(prog, opts)
        k = opts.inject_slots
        inj_t = jnp.full((k,), -1, jnp.int32)
        inj_w = jnp.zeros((1 + opts.msg_words, k), jnp.int32)
        return str(jax.make_jaxpr(step)(st, inj_t, inj_w))

    baseline = build(0)

    def boom(*_a, **_k):
        raise AssertionError("profiler lanes traced at analysis=0")

    monkeypatch.setattr(lanes, "profile_lanes", boom)
    assert build(0) == baseline     # trap unreached, jaxpr bit-identical
    with pytest.raises(AssertionError, match="lanes traced"):
        build(1)                    # and it IS the only lane source
    monkeypatch.undo()

    # Same guarantee for the per-phase tick-cost lanes (ISSUE 19): the
    # observatory must be jaxpr-bit-identical when off, and
    # phase_cost_lanes must be the lanes' only source when on.
    def boom2(*_a, **_k):
        raise AssertionError("phase lanes traced at analysis=0")

    monkeypatch.setattr(lanes, "phase_cost_lanes", boom2)
    assert build(0) == baseline
    with pytest.raises(AssertionError, match="phase lanes traced"):
        build(1)


def test_phase_lanes_count_ring_work():
    """Per-phase window telemetry (ISSUE 19): a 50-hop single-token
    ring delivers/drains/dispatches exactly one work unit per hop and
    marks nothing (no spawns or exits until the last hop's self.exit),
    and the phases ride Runtime.profile()."""
    rt, ids = ring.build(8, _opts(analysis=1))
    rt.send(int(ids[0]), ring.RingNode.token, 50)
    rt.run()
    ph = rt.profile()["phases"]
    assert ph["delivery"] == ph["drain"] == ph["dispatch"] == 50
    # exit(0) requests world exit — no device spawn/destroy happened
    assert ph["gc_mark"] == 0
    rt.stop()


@actor
class Leaf:
    hub: Ref[Worker]

    @behaviour
    def poke(self, st, v: I32):
        self.send(st["hub"], Worker.work, v)
        return st


@pytest.mark.parametrize("shards", [1, 4], ids=["one-shard", "mesh4"])
def test_rebuild_lane_counts_slots_gathered(shards):
    """The `rebuild` lane is, over the cohorts, the indices the blocks
    THAT cohort ran read over its local rows (`block_indices`), summed over
    ticks and shards: 20 leaves (poked in bulk, straight into their
    rings) send to one hub in one tick — acc = 20 at mailbox_cap 32,
    three blocks for the one row of Worker's on the hub's shard, none
    over Leaf's and none elsewhere; later 5 leaves are poked through the
    delivery list (one block of Leaf's rows on each shard that holds one
    of them, as wide as the leaves it holds there and, full width, ONE
    rank deep: a leaf holds its one poke) and send (one block of
    Worker's); ticks that deliver nothing gather nothing."""
    rt = Runtime(_opts(mailbox_cap=32, batch=2, analysis=1,
                       inject_slots=32, mesh_shards=shards))
    rt.declare(Worker, 1).declare(Leaf, 20).start()
    hub = rt.spawn(Worker)
    leaves = rt.spawn_many(Leaf, 20, hub=hub)
    rows = {ch.atype.__name__: ch.local_stop - ch.local_start
            for ch in rt.program.cohorts}
    assert rows["Leaf"] * shards == 20
    assert sum(rows.values()) == rt.program.n_local
    rt.bulk_send(leaves, Leaf.poke, np.ones(20, np.int32))
    assert rt.run() == 0
    hub_slots = tick_indices(rows["Worker"], [20])  # blocks of 8, 8, 4
    assert rt.profile()["phases"]["rebuild"] == hub_slots == (
        3 * block_indices(rows["Worker"], 1, 8))
    for leaf in leaves[:5]:
        rt.send(int(leaf), Leaf.poke, 1)
    assert rt.run() == 0
    hub_slots += tick_indices(rows["Worker"], [5])
    poked = np.bincount([int(leaf) // rt.program.n_local
                         for leaf in leaves[:5]], minlength=shards)
    assert (poked > 0).sum() == min(shards, 5)
    assert rt.profile()["phases"]["rebuild"] == hub_slots + sum(
        block_indices(rows["Leaf"], int(k), 1) for k in poked if k)
    if shards == 1:         # 5 of 20 rows past M = 3: full width, 1 rank
        assert block_indices(rows["Leaf"], 5, 1) == 20
    assert rt.state_of(hub)["done"] == 25
    rt.stop()


def test_rebuild_lane_falls_where_few_rows_are_deep():
    """A later block reads for the rows that have a message in it: 64
    workers all take a message in one tick and one of them takes 20, so
    the first block is full width (the fullest row's 8 ranks of it x
    64) and the two later ones, with one deep row against M = 8, are
    compacted: 8 x 8 each, where a full block of 8 ranks reads 8 x 64."""
    rt = Runtime(_opts(mailbox_cap=32, batch=2, analysis=1))
    rt.declare(Worker, 64).declare(Leaf, 83).start()
    workers = rt.spawn_many(Worker, 64)
    hubs = np.concatenate([np.full(20, workers[0]), workers[1:]])
    leaves = rt.spawn_many(Leaf, 83, hub=hubs)
    rt.bulk_send(leaves, Leaf.poke, np.ones(83, np.int32))
    assert rt.run() == 0
    got = rt.profile()["phases"]["rebuild"]
    assert got == tick_indices(64, [20] + 63 * [1]) == (
        block_indices(64, 64, 8) + block_indices(64, 1, 8)
        + block_indices(64, 1, 4)) == 512 + 2 * 64
    assert got < 3 * 8 * 64
    assert rt.state_of(int(workers[0]))["done"] == 20
    rt.stop()


def test_one_block_ring_counts_its_whole_ring():
    """`mailbox_cap <= REBUILD_BLOCK`: one block of `cap` ranks on every
    tick that delivers (the 50 hops of the ring's token)."""
    rt, ids = ring.build(8, _opts(mailbox_cap=4, analysis=1))
    rt.send(int(ids[0]), ring.RingNode.token, 50)
    rt.run()
    ph = rt.profile()["phases"]
    assert ph["rebuild"] == ph["delivery"] * 4 * rt.program.n_local
    rt.stop()


# ------------------------------------------------------- GC window stats

def test_gc_window_stats_thread_into_profile_and_csv(tmp_path):
    @actor
    class Kid:
        x: I32

        @behaviour
        def init(self, st, v: I32):
            return {**st, "x": v}

    @actor
    class Boss:
        SPAWNS = {"Kid": 1}
        made: I32

        @behaviour
        def make(self, st, v: I32):
            self.spawn(Kid.init, v)
            return {**st, "made": st["made"] + 1}

    path = str(tmp_path / "gc.csv")
    rt = Runtime(_opts(msg_words=2, analysis=2, analysis_path=path))
    rt.declare(Boss, 1).declare(Kid, 8).start()
    boss = rt.spawn(Boss)
    for v in range(3):
        rt.send(boss, Boss.make, v)
    rt.run()
    collected = rt.gc()     # spawned Kids are unreferenced → collected
    assert collected == 3
    # One more window so the CSV sees the gc deltas.
    rt.send(boss, Boss.make, 9)
    rt.run()
    prof = rt.profile()
    assert prof["gc"]["passes"] >= 1
    assert prof["gc"]["collected"] >= 3
    assert "blob_slots_reclaimed" in prof["gc"]
    rt.stop()
    lines = open(path).read().strip().split("\n")
    header = lines[0].split(",")
    for col in ("gc_runs", "gc_collected", "gc_swept", "ev_dropped"):
        assert col in header
    rows = [dict(zip(header, l.split(","))) for l in lines[1:]]
    assert sum(int(r["gc_runs"]) for r in rows) >= 1
    assert sum(int(r["gc_collected"]) for r in rows) >= 3


# ------------------------------------------- chrome trace / CLI surfaces

def test_chrome_trace_per_behaviour_tracks(tmp_path):
    """Acceptance: chrome_trace output carries one counter track per
    hot behaviour and validates against the Chrome-trace JSON schema
    Perfetto loads."""
    path = str(tmp_path / "an.csv")
    rt, ids = ring.build(8, _opts(analysis=2, analysis_path=path))
    rt.send(int(ids[0]), ring.RingNode.token, 40)
    rt.run()
    rt.stop()
    out = str(tmp_path / "t.json")
    analysis.chrome_trace(path, out)
    doc = json.load(open(out))
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    for e in evs:        # minimal Perfetto/Chrome-trace event schema
        assert e["ph"] in ("M", "C", "i")
        assert isinstance(e["pid"], int)
        assert isinstance(e["name"], str)
        if e["ph"] != "M":
            assert isinstance(e["ts"], float)
        if e["ph"] == "C":
            assert all(isinstance(v, int) for v in e["args"].values())
    beh = [e for e in evs
           if e["ph"] == "C" and e["name"] == "behaviour RingNode.token"]
    assert beh, "no per-behaviour counter track"
    assert sum(e["args"]["runs"] for e in beh) == 40
    qw = [e for e in evs
          if e["ph"] == "C" and e["name"] == "queue-wait RingNode"]
    assert qw and all(set(e["args"]) == {"p50", "p99"} for e in qw)


def test_chrome_trace_pre_profiler_csv(tmp_path):
    """Old CSVs (no dynamic columns) still convert — the trace CLI must
    work on files written by earlier runtimes."""
    path = str(tmp_path / "old.csv")
    cols = ["time_ms", "step", "processed", "delivered", "rejected",
            "badmsg", "deadletter", "mutes", "occ_sum", "occ_max",
            "muted_now", "overloaded_now", "host_processed",
            "inject_queue", "fast_queue", "rss_kb", "cpu_ms"]
    with open(path, "w") as f:
        f.write(",".join(cols) + "\n")
        f.write(",".join(["1.0", "1"] + ["2"] * (len(cols) - 2)) + "\n")
    out = str(tmp_path / "old.json")
    analysis.chrome_trace(path, out)
    doc = json.load(open(out))
    assert any(e["name"] == "window throughput"
               for e in doc["traceEvents"])


def test_trace_cli(tmp_path):
    """The `ponyc_tpu trace` subcommand: conversion + usage errors."""
    from ponyc_tpu.__main__ import main as cli_main
    path = str(tmp_path / "an.csv")
    rt, ids = ring.build(8, _opts(analysis=2, analysis_path=path))
    rt.send(int(ids[0]), ring.RingNode.token, 10)
    rt.run()
    rt.stop()
    out = str(tmp_path / "cli.json")
    assert cli_main(["trace", path, "-o", out]) == 0
    assert json.load(open(out))["traceEvents"]
    assert cli_main(["trace"]) == 2            # missing csv
    assert cli_main(["trace", "-o"]) == 2      # -o without a path


def test_top_frame_and_cli(tmp_path, capsys):
    path = str(tmp_path / "an.csv")
    rt, ids = ring.build(8, _opts(analysis=2, analysis_path=path))
    rt.send(int(ids[0]), ring.RingNode.token, 30)
    rt.run()
    rt.stop()
    frame = analysis.top_frame(path)
    assert "RingNode.token" in frame
    assert "queue-wait" in frame
    assert "step " in frame and "gc:" in frame
    from ponyc_tpu.__main__ import main as cli_main
    assert cli_main(["top", path, "--once"]) == 0
    out = capsys.readouterr().out
    assert "RingNode.token" in out
    # usage errors
    assert cli_main(["top", "--interval"]) == 2
    assert cli_main(["top", "--interval", "nope"]) == 2
    assert cli_main(["top", "a.csv", "b.csv"]) == 2
    # a missing file waits rather than crashing
    assert cli_main(["top", str(tmp_path / "absent.csv"),
                     "--once"]) == 0
    assert "waiting" in capsys.readouterr().out


def test_top_frame_empty_csv(tmp_path):
    path = str(tmp_path / "empty.csv")
    with open(path, "w") as f:
        f.write(",".join(analysis.CSV_COLUMNS) + "\n")
    assert "no windows" in analysis.top_frame(path)


# ------------------------------------------------- signal / CLI smokes

def test_sigterm_dumps_then_terminates(tmp_path):
    """Satellite fix: after a level-1 dump on SIGTERM the handler
    restores the default disposition and re-raises, so the process
    actually dies of SIGTERM (the old lambda swallowed it forever)."""
    code = f"""
import os, signal, sys
sys.path.insert(0, {ROOT!r})
from ponyc_tpu.platforms import force_cpu
force_cpu()
from ponyc_tpu import RuntimeOptions, analysis
from ponyc_tpu.models import ring
rt, ids = ring.build(4, RuntimeOptions(
    mailbox_cap=8, batch=1, max_sends=1, msg_words=1, analysis=1))
rt.send(int(ids[0]), ring.RingNode.token, 5)
rt.run()
a = analysis.attach(rt)
os.kill(os.getpid(), signal.SIGTERM)
print("SURVIVED-SIGTERM")
"""
    p = _child.script(code)
    assert p.returncode == -signal.SIGTERM, (p.returncode, p.stderr)
    assert "ponyc_tpu analysis dump" in p.stderr
    assert "SURVIVED-SIGTERM" not in p.stdout


@pytest.mark.parametrize("flush_ms", [-1])
def test_analysis_flush_ms_validated(flush_ms):
    with pytest.raises(ValueError, match="analysis_flush_ms"):
        RuntimeOptions(analysis_flush_ms=flush_ms)


def test_example_smoke_analysis2(tmp_path):
    """Tier-1 smoke: run a shipped example through the CLI at
    analysis=2 and validate the window CSV schema end to end,
    including the per-behaviour columns (satellite)."""
    path = str(tmp_path / "counter.csv")
    p = _child.cli(["run", os.path.join(ROOT, "examples", "counter.py"),
                    "--ponyanalysis=2", f"--ponyanalysis_path={path}"])
    assert p.returncode == 0, (p.stdout, p.stderr)
    lines = open(path).read().strip().split("\n")
    header = lines[0].split(",")
    assert header[:len(analysis.CSV_COLUMNS)] == analysis.CSV_COLUMNS
    for col in ("run:Counter.increment", "run:Counter.report",
                "run:Reporter.result", "qw50:Counter", "qw99:Counter"):
        assert col in header, col
    rows = [dict(zip(header, l.split(","))) for l in lines[1:]]
    # 8 counters × (100 increments sent as 25 messages of +4) = 200
    assert sum(int(r["run:Counter.increment"]) for r in rows) == 200
    assert sum(int(r["run:Counter.report"]) for r in rows) == 8
    # the dump summary (level >= 1) ran on exit too
    assert "analysis dump" in p.stderr


# ------------------------------------- named scopes on the tick (ISSUE 24)

# plan / cosort at a one-block ring, and a ring deeper than one rebuild
# block, whose rebuild is a loop (delivery.rebuild_tables).
WINDOWS = [("plan", 4), ("cosort", 4), ("plan", 16)]
WINDOW_IDS = ["plan", "cosort", "plan-deep-cap"]


def _lowered_window(delivery, cap):
    import jax
    import jax.numpy as jnp

    from ponyc_tpu.models import ubench
    from ponyc_tpu.runtime import engine
    opts = _opts(mailbox_cap=cap, batch=2, delivery=delivery,
                 tuning_cache="off", compile_cache="off")
    rt, _ids = ubench.build(64, opts, pings=2)
    gated = engine.build_multi_step_gated(rt.program, rt.opts)
    lowered = jax.jit(gated).lower(
        rt.state, *rt._empty_inject, jnp.int32(4), jnp.bool_(True),
        rt._zero_aux)
    rt.stop()
    return rt, lowered


@pytest.mark.parametrize("delivery,cap", WINDOWS, ids=WINDOW_IDS)
def test_phase_scopes_name_the_lowered_window(delivery, cap):
    """Every scope of the vocabulary (state.STEP_SCOPES) appears in the
    lowered window's op_name metadata, under both delivery formulations;
    `pony/gc_mark` also heads the collection pass's own program. The
    rebuild's loop body is a computation of its own: its operations
    carry `pony/delivery/rebuild` themselves, and the compacted blocks'
    (a second loop, a ring deeper than one block only) the sub-scope
    `pony/delivery/rebuild/compact`."""
    import jax

    from ponyc_tpu.runtime import gc as gc_mod
    from ponyc_tpu.runtime.state import SCOPE_PREFIX, STEP_SCOPES
    rt, lowered = _lowered_window(delivery, cap)
    text = lowered.as_text(debug_info=True)
    # `dispatch/heap` names the blob pool's operations: a blob-free
    # world has none (tests/test_gups.py holds the world that has, and
    # tests/test_taskbench_payload.py the five scopes below it);
    # `spawn/*` a world whose behaviours create actors, `gc_mark/*` the
    # collector's own program (tests/test_spreader.py holds both),
    # `route/*` a mesh's window (tests/test_mesh_ubench.py holds it)
    elsewhere = ("gc_mark", "dispatch/heap", "dispatch/heap/get",
                 "dispatch/heap/set", "dispatch/heap/alloc",
                 "dispatch/heap/free", "dispatch/heap/reserve",
                 "spawn/free", "spawn/reserve",
                 "spawn/claim", "gc_mark/roots", "gc_mark/hop",
                 "gc_mark/sweep", "route/sort", "route/bucket",
                 "route/exchange", "route/spill", "route/spill/lookup",
                 "route/spill/mute", "route/unpack") + (
                     () if cap > 8 else ("delivery/rebuild/compact",))
    missing = [s for s in STEP_SCOPES if s not in elsewhere
               and f"{SCOPE_PREFIX}/{s}/" not in text]
    assert not missing, missing
    assert f"{SCOPE_PREFIX}/dispatch/heap" not in text
    assert f"{SCOPE_PREFIX}/route/" in text
    for sub in ("sort", "bucket", "exchange", "spill", "unpack"):
        assert f"{SCOPE_PREFIX}/route/{sub}" not in text, sub
    in_body = "rebuild/while/body/pony/delivery/rebuild/"
    assert (in_body in text) == (cap > 8)
    compact_body = "rebuild/while/body/pony/delivery/rebuild/compact/"
    assert (compact_body in text) == (cap > 8)
    if cap > 8:
        assert in_body + "jit(_take)" in text, "the body's gather"
        assert compact_body + "jit(_take)" in text, "the compacted body's"
    import numpy as np
    nl = rt.program.n_local
    gc_text = jax.jit(gc_mod.build_gc(rt.program, rt.opts)).lower(
        rt.state, np.zeros((nl,), bool),
        np.zeros((max(1, rt.opts.blob_slots),), bool)
    ).as_text(debug_info=True)
    assert f"{SCOPE_PREFIX}/gc_mark/" in gc_text


@pytest.mark.parametrize("delivery,cap", WINDOWS, ids=WINDOW_IDS)
def test_phase_scopes_are_metadata_only(delivery, cap, monkeypatch):
    """The optimised HLO of the window is the same program with the
    scopes and with the one scope helper stubbed out, once metadata is
    stripped: the names cost the compiled program nothing."""
    import contextlib

    from ponyc_tpu.runtime import state
    _rt, lowered = _lowered_window(delivery, cap)
    scoped = lowered.compile().as_text()
    assert 'op_name="jit(multi)/while/body/pony/delivery' in scoped
    monkeypatch.setattr(state, "_named_scope",
                        lambda _name: contextlib.nullcontext())
    _rt, lowered = _lowered_window(delivery, cap)
    bare = lowered.compile().as_text()
    assert "pony/" not in bare
    assert bare_hlo(scoped) == bare_hlo(bare)


def test_profiler_trace_holds_the_run_phases(tmp_path):
    """A jax.profiler trace of run() on the CPU backend contains the
    run loop's `pony:*` spans, on the profiler's clock, carrying the
    window they belong to."""
    import glob

    import jax
    from jax.profiler import ProfileData
    rt, ids = ring.build(8, _opts(tuning_cache="off"))
    rt.send(int(ids[0]), ring.RingNode.token, 50)
    rt.run()                                  # compile outside the trace
    rt._exit_code = 0
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        rt.send(int(ids[0]), ring.RingNode.token, 50)
        rt.run()
    finally:
        jax.profiler.stop_trace()
    rt.stop()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("pony:"):
                    spans.setdefault(e.name, []).append(dict(e.stats))
    assert {"pony:enter", "pony:dispatching", "pony:wait",
            "pony:host-work", "pony:exit"} <= set(spans)
    assert all("window" in s for s in spans["pony:dispatching"])
    assert any(s.get("ticks") for s in spans["pony:host-work"])
