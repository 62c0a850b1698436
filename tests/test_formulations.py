"""The three formulation switches — `delivery` in {"plan", "cosort"},
`pallas`, `pallas_fused` — are plain values that one gate
(engine.check_kernels) honours or refuses; nothing is raced, resolved
or remembered at start() but the window length.

What `chip_smoke.py` phase (d) checks on the chip for one world, here
on the CPU backend (Pallas in interpret mode) for six: every state leaf
after the run loop's gated window equals plan's, under each switch. A
world a kernel cannot serve asserts the refusal's words instead.
"""

import dataclasses
import os
import sys

import jax
import numpy as np
import pytest
from jax import monitoring

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from _rebuild import block_indices  # noqa: E402
from chip_smoke import PLAN_CACHE_LEAVES  # noqa: E402
from ponyc_tpu import (I32, Ref, Runtime, RuntimeOptions, actor,  # noqa: E402
                       behaviour)
from ponyc_tpu.config import options_from_env, strip_runtime_flags  # noqa: E402
from ponyc_tpu.models import fanin, mixed, ring, ubench  # noqa: E402
from ponyc_tpu.ops import mailbox_kernel  # noqa: E402
from ponyc_tpu.runtime import engine  # noqa: E402
from ponyc_tpu.runtime.state import PHASE_NAMES  # noqa: E402

WINDOW = 4          # quiesce_interval: the gated window's fixed length

FORMULATIONS = {"cosort": dict(delivery="cosort"),
                "pallas": dict(pallas=True),
                "pallas_fused": dict(pallas_fused=True)}


def _opts(**kw):
    base = dict(msg_words=1, max_sends=1, spill_cap=256, inject_slots=8,
                quiesce_interval=WINDOW, tuning_cache="off",
                compile_cache="off")
    base.update(kw)
    return RuntimeOptions(**base)


# ------------------------------------------------------------ the worlds
# Each takes the formulation's overrides and returns the Runtime after
# its run: built, seeded and advanced through Runtime.run().


def _ubench(**form):
    rt, ids = ubench.build(64, _opts(mailbox_cap=4, batch=4, **form),
                           pings=4)
    ubench.seed_all(rt, ids, hops=1 << 30, pings=4)
    assert rt.run(max_steps=3 * WINDOW) == 0
    assert rt.steps_run == 3 * WINDOW
    return rt


def _fanin(cap, producers, items, ticks, **form):
    rt = Runtime(_opts(mailbox_cap=cap, batch=2, max_sends=2, **form))
    rt.declare(fanin.Producer, producers).declare(fanin.Aggregator, 1)
    rt.start()
    agg = rt.spawn(fanin.Aggregator)
    ids = rt.spawn_many(fanin.Producer, producers, out=agg)
    rt.bulk_send(ids, fanin.Producer.produce, [items] * producers)
    assert rt.run(max_steps=ticks) == 0
    return rt


def _fanin_pressure(**form):
    """32 producers onto one aggregator of capacity 8: reject, spill,
    mute and unmute on every tick."""
    rt = _fanin(8, 32, 6, 6 * WINDOW, **form)
    assert rt.counter("n_rejected") > 0 and rt.counter("n_mutes") > 0
    return rt


def _fanin_deep(**form):
    """20 producers onto a ring of 32: the first delivery accepts 20 at
    once, three rebuild blocks of 8."""
    return _fanin(32, 20, 3, 3 * WINDOW, **form)


def _ring(**form):
    rt, ids = ring.build(16, _opts(mailbox_cap=8, batch=1, **form))
    rt.send(int(ids[0]), ring.RingNode.token, 40)
    assert rt.run() == 0                       # to quiescence
    assert int(rt.cohort_state(ring.RingNode)["passes"].sum()) == 40
    return rt


def _mixed(**form):
    rt, ids, wt = mixed.build(32, 3, _opts(mailbox_cap=4, batch=2, **form),
                              pings=2)
    mixed.seed_all(rt, ids, wt, hops=1 << 20, pings=2, mix=True)
    assert rt.run(max_steps=3 * WINDOW) == 0
    return rt


@actor
class Kid:
    tag: I32

    @behaviour
    def init(self, st, tag: I32):
        return {**st, "tag": tag}

    @behaviour
    def probe(self, st, bump: I32):
        return {**st, "tag": st["tag"] + bump}


@actor
class Maker:
    made: Ref
    MAX_SENDS = 1
    SPAWNS = {"Kid": 1}

    @behaviour
    def make(self, st, v: I32):
        ref = self.spawn_sync(Kid.init, v)
        self.send(ref, Kid.probe, 100, when=ref >= 0)
        return {**st, "made": ref}


def _spawning(**form):
    """Four makers construct a Kid each, synchronously, and probe it."""
    rt = Runtime(_opts(mailbox_cap=8, batch=2, msg_words=2, **form))
    rt.declare(Maker, 4).declare(Kid, 8).start()
    makers = rt.spawn_many(Maker, 4)
    rt.bulk_send(makers, Maker.make, [7, 8, 9, 10])
    assert rt.run(max_steps=2 * WINDOW) == 0
    assert rt.counter("n_spawned") == 4
    return rt


@actor
class Mortal:
    seen: I32

    @behaviour
    def poke(self, st, v: I32):
        self.destroy(when=st["seen"] >= 2)        # dies on its third poke
        return {**st, "seen": st["seen"] + 1}


@actor
class Poker:
    target: Ref
    MAX_SENDS = 2

    @behaviour
    def go(self, st, left: I32):
        self.send(st["target"], Mortal.poke, left)
        self.send(self.actor_id, Poker.go, left - 1, when=left > 1)
        return st


def _dying(**form):
    """Six pokers poke two Mortals for eight ticks; a Mortal takes three
    pokes a tick and runs one, so it is overloaded (its pokers mute) by
    the tick it dies on its third poke, with six more queued and the
    next tick's in flight. Every send to the dead row is a dead letter:
    counted, never delivered, never spilled, never a mute
    (tests/test_guards.py has the semantics tick by tick). The
    formulations of ONE tree agree on every leaf; `plan_key`,
    `plan_perm` and `plan_bounds` of such a tick are not the parent
    commit's (PR 30: a dead-lettered entry sorts inside its row's
    segment, not after the last row)."""
    rt = Runtime(_opts(mailbox_cap=8, batch=1, max_sends=2, **form))
    rt.declare(Mortal, 2).declare(Poker, 6).start()
    mortals = rt.spawn_many(Mortal, 2)
    pokers = rt.spawn_many(Poker, 6, target=np.repeat(mortals, 3))
    rt.bulk_send(pokers, Poker.go, [8] * 6)
    assert rt.run() == 0
    assert rt.counter("n_destroyed") == 2
    # 48 pokes: each Mortal ran 3, had 6 more queued when it died
    # (discarded with the slot), and the other 15 found it dead
    assert rt.counter("n_deadletter") == 30
    assert rt.counter("n_rejected") == 0 and rt.counter("n_mutes") == 6
    return rt


WORLDS = {"ubench": _ubench, "fanin-pressure": _fanin_pressure,
          "fanin-deep": _fanin_deep, "ring": _ring, "mixed": _mixed,
          "spawning": _spawning, "dying": _dying}

# (world, formulation) -> the refusal the gate must raise
REFUSED = {("spawning", "pallas_fused"):
           "pallas_fused=True cannot be honoured — cohort Maker: a "
           "behaviour constructs actors synchronously"}


def _leaves(rt):
    leaves = {jax.tree_util.keystr(path): np.asarray(leaf) for path, leaf
              in jax.tree_util.tree_flatten_with_path(rt.state)[0]}
    rt.stop()
    return leaves


_plan = {}


def _plan_leaves(world, **kw):
    key = (world, tuple(sorted(kw.items())))
    if key not in _plan:
        _plan[key] = _leaves(WORLDS[world](**kw))
    return _plan[key]


def _assert_equals_plan(world, formulation, **kw):
    plan = _plan_leaves(world, **kw)
    got = _leaves(WORLDS[world](**FORMULATIONS[formulation], **kw))
    assert set(got) == set(plan)
    skip = PLAN_CACHE_LEAVES if formulation == "cosort" else ()
    compared = [k for k in plan if not any(s in k for s in skip)]
    assert len(compared) >= len(plan) - len(PLAN_CACHE_LEAVES)
    assert [k for k in compared
            if not np.array_equal(plan[k], got[k])] == []
    return got


@pytest.mark.parametrize("formulation", list(FORMULATIONS))
@pytest.mark.parametrize("world", list(WORLDS))
def test_window_equals_plan_on_every_leaf(world, formulation):
    if (world, formulation) in REFUSED:
        with pytest.raises(ValueError) as exc:
            WORLDS[world](**FORMULATIONS[formulation])
        assert str(exc.value) == REFUSED[(world, formulation)]
        return
    _assert_equals_plan(world, formulation)


@pytest.mark.parametrize("formulation", list(FORMULATIONS))
def test_profiler_lanes_equal_plans_at_analysis_1(formulation):
    """The profiler's lanes (lanes.profile_lanes, phase_cost_lanes) are
    recomputed from facts every formulation produces: equal like every
    other leaf, and counting — the deep fan-in's rebuild lane among
    them."""
    got = _assert_equals_plan("fanin-deep", formulation, analysis=1)
    for lane in ("beh_runs", "beh_delivered", "qwait_hist", "phase_cost"):
        assert got["." + lane].sum() > 0, lane
    cost = dict(zip(PHASE_NAMES, got[".phase_cost"]))
    assert cost["delivery"] > 0 and cost["drain"] == cost["dispatch"] > 0
    # A block reads over a COHORT's rows, as wide as those with a message
    # in it (tests/_rebuild.py): the first delivery alone took three
    # blocks of the aggregator's one row and one full-width block of the
    # producers' 20, one rank deep (ISSUE 52) — and the whole run less
    # than that delivery cost when all 21 rows went as deep as the
    # aggregator (ISSUE 36)
    assert (3 * block_indices(1, 1, 8) + block_indices(20, 20, 1)
            <= cost["rebuild"] < 8 * 3 * 21)


# ------------------------------------------- values that no longer exist

def _env(monkeypatch, name, value):
    monkeypatch.setenv(name, value)
    return options_from_env()


# The removed names are spelled in halves so that a grep of the tree for
# them finds nothing (ISSUE 29's acceptance check).
MEGA = "pallas_" + "mega"
GONE_FIELDS = ("tuning_" + "ticks", "tuning_" + "repeats",
               "dispatch_" + "gating")

REMOVED = {
    "delivery=auto": (lambda mp: RuntimeOptions(delivery="auto"),
                      ValueError, "'plan' or 'cosort'"),
    "delivery=mega": (
        lambda mp: RuntimeOptions(delivery=MEGA),
        ValueError, "'plan' or 'cosort'"),
    "pallas=auto": (lambda mp: RuntimeOptions(pallas="auto"),
                    ValueError, "pallas must be True or False"),
    "pallas_fused=auto": (lambda mp: RuntimeOptions(pallas_fused="auto"),
                          ValueError,
                          "pallas_fused must be True or False"),
    "env-delivery=auto": (lambda mp: _env(mp, "PONY_TPU_DELIVERY", "auto"),
                          ValueError, "'plan' or 'cosort'"),
    "env-pallas=auto": (lambda mp: _env(mp, "PONY_TPU_PALLAS", "auto"),
                        ValueError, "pallas must be true or false"),
    "flag-pallas_fused=auto": (
        lambda mp: strip_runtime_flags(["app", "--ponypallas_fused=auto"]),
        ValueError, "pallas_fused must be true or false"),
    "field-ticks": (lambda mp: RuntimeOptions(**{GONE_FIELDS[0]: 2}),
                    TypeError, GONE_FIELDS[0]),
    "field-gating": (lambda mp: RuntimeOptions(**{GONE_FIELDS[2]: True}),
                     TypeError, GONE_FIELDS[2]),
}


@pytest.mark.parametrize("case", list(REMOVED))
def test_removed_values_are_rejected(case, monkeypatch):
    make, exc, words = REMOVED[case]
    with pytest.raises(exc, match=words):
        make(monkeypatch)


def test_delivery_option_validation():
    for ok in ("plan", "cosort"):
        assert RuntimeOptions(delivery=ok).delivery == ok
    assert RuntimeOptions().delivery == "plan"
    with pytest.raises(ValueError, match="'plan' or 'cosort'"):
        RuntimeOptions(delivery="cosortt")
    fields = {f.name for f in dataclasses.fields(RuntimeOptions)}
    assert {"delivery", "pallas", "pallas_fused"} <= fields
    assert not set(GONE_FIELDS) & fields


# ------------------------------------------------------------- one gate

@actor
class BlobUser:
    n: I32
    MAX_BLOBS = 1

    @behaviour
    def grab(self, st):
        self.blob_alloc(length=1)
        return st


UNALIGNED = mailbox_kernel.LANE_BLOCK + 8     # > one block, no multiple


def _untileable():
    rt = Runtime(_opts(mailbox_cap=4, batch=4, pallas=True))
    rt.declare(ubench.Pinger, UNALIGNED)
    return rt, (f"pallas=True cannot be honoured — cohort Pinger: "
                f"{UNALIGNED} rows per shard is neither <= "
                f"{mailbox_kernel.LANE_BLOCK} nor a multiple of it (the "
                "kernel's lane block)")


def _blob_cohort():
    rt = Runtime(_opts(mailbox_cap=4, batch=2, msg_words=2, blob_slots=8,
                       blob_words=4, pallas_fused=True))
    rt.declare(BlobUser, 8)
    return rt, ("pallas_fused=True cannot be honoured — cohort BlobUser: "
                "uses the device blob pool")


@pytest.mark.parametrize("entry", ["start", "build_step"])
@pytest.mark.parametrize("kernel", [_untileable, _blob_cohort])
def test_one_gate(kernel, entry):
    """The same words from Runtime.start() and from engine.build_step
    called by hand: one function refuses for both."""
    rt, words = kernel()
    with pytest.raises(ValueError) as exc:
        if entry == "start":
            rt.start()
        else:
            rt.program.finalize()
            engine.build_step(rt.program, rt.opts)
    assert str(exc.value) == words


# ------------------------------------- nothing at start() but the window

def test_start_records_only_the_window():
    compiles = [0]

    def on(event, _secs, **_kw):
        compiles[0] += event == "/jax/core/compile/backend_compile_duration"

    monitoring.register_event_duration_secs_listener(on)

    def started():
        rt = Runtime(_opts(mailbox_cap=4, batch=4,
                           quiesce_interval="auto"))
        rt.declare(ubench.Pinger, 64)
        before = compiles[0]
        rt.start()
        return rt, compiles[0] - before

    started()                  # the set-up's small programs, once
    rt, in_start = started()
    assert in_start == 0       # start() compiled nothing of its own
    assert rt.tuning_record == {"quiesce_interval": {
        "bounds": [4, 1024], "source": "default", "initial": 64}}
    assert (rt.opts.delivery, rt.opts.pallas, rt.opts.pallas_fused) \
        == ("plan", False, False)
    ids = rt.spawn_many(ubench.Pinger, 64)
    rt.bulk_send(ids, ubench.Pinger.ping, [1 << 20] * 64)
    before = compiles[0]
    assert rt.run(max_steps=8) == 0
    # the run loop called one executable, the gated window, and start()
    # had traced none
    assert compiles[0] - before == 1
    assert (rt._step._cache_size(), rt._multi_g._cache_size()) == (0, 1)
    rt.stop()
