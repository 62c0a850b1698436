"""Upstream's `spreader` run back to back by many roots
(`benchmarks/worlds/spreader.py`, the world of the cell
`spreader-forest.churn`) against its plain reference
(`benchmarks/reference_spreader.py`), on the CPU at small sizes: trees of
count 3-6, 8-32 roots, 256-8,192 rows.

Behaviours create actors here and only the collector frees them, so this
is also where spawn reservation, the run loop's row-pressure collection
and the collector's trace are held to their word.
"""

import json
import os

import jax
import numpy as np
import pytest

from benchmarks import reference_spreader as ref
from benchmarks.worlds import spreader
from ponyc_tpu import (I32, Ref, Runtime, RuntimeOptions, SpawnCapacityError,
                       actor, behaviour)
from ponyc_tpu.runtime import engine, gc as gc_mod
from ponyc_tpu.runtime.state import SCOPE_PREFIX

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS, DELIVERIES = [0, 1, 2], ["plan", "cosort"]
QUIET = dict(compile_cache="off", tuning_cache="off")


def _world(seed, actors, count, *, traffic=(), **options):
    with open(os.path.join(ROOT,
                           "benchmarks/configs/spreader-forest.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmarks/traffic/churn.json")) as f:
        mix = json.load(f)
    cfg.update(actors=actors, count=count)
    cfg["runtime_options"] = {**cfg["runtime_options"], **QUIET, **options}
    return spreader.build(
        cfg, {**mix, "count": count, "phases": 2 * count, **dict(traffic)},
        seed)


def _clean(world, tick=None) -> dict:
    found = world.check()
    assert not any(found[k] for k in ("roots_off", "spawned_off",
                                      "rows_off", "lost")), (tick, found)
    assert not any(world.errors().values()), (tick, world.errors())
    return found


@pytest.mark.parametrize("delivery", DELIVERIES)
@pytest.mark.parametrize("seed", SEEDS)
def test_world_equals_the_forest_tick_by_tick(seed, delivery):
    """Three periods past the longest phase, one tick at a time: every
    tick spawns and dispatches what `Forest` says, the invariant holds
    after every pass (no live actor collected, every root's total = runs
    x the tree's actors), and after a forced pass alive == reachable,
    row for row. The world is sized so that the parent's reservation
    rule (every alive row reserves: alive <= rows / 3) would have refused
    a spawn: it runs clean."""
    world = _world(seed, 2048, 5, delivery=delivery)
    rt, forest = world.rt, world.reference()
    assert world.roots == 20 and world.period == 10
    spawned = processed = passes = 0
    peak = 0
    for tick in range(1, 4 * world.period + 1):
        assert rt.run(max_steps=1) == 0
        want = forest.tick()
        now = world.counters()
        done = rt.counter("n_processed")
        assert (now["spawned"] - spawned, done - processed) \
            == (want["spawns"], want["dispatches"]), tick
        spawned, processed = now["spawned"], done
        peak = max(peak, int(np.asarray(rt.state.alive).sum()))
        if now["passes"] != passes:
            passes = now["passes"]
            assert _clean(world, tick)["garbage"] == 0
            assert int(np.asarray(rt.state.alive).sum()) \
                == want["live"] + world.roots
    assert passes >= 2 and peak > world.n // 3
    assert world.held() == forest.held()
    _clean(world)
    rt.gc()
    found = _clean(world)
    assert found["garbage"] == 0 and found["lost"] == 0
    assert now["free_rows_low"] is not None and now["free_rows_low"] >= 0
    rt.stop()


def test_one_root_of_count_10_reports_2047_actors():
    """Upstream's run: one tree of count 10, to quiescence. The root
    reports 2,047 actors, as upstream prints it; 2,046 were created by
    behaviours, and once the root is released a pass leaves no one."""
    rt = Runtime(RuntimeOptions(mailbox_cap=8, spill_cap=64, msg_words=2,
                                inject_slots=8, **QUIET))
    rt.declare(spreader.Spreader, 8192).start()
    root = rt.spawn(spreader.Spreader, parent=-1, depth=10, left=1)
    rt.send(root, spreader.Spreader.start, 0)
    assert rt.run() == 0
    st = rt.state_of(root)
    assert (st["runs"], st["total"]) == (1, 2047) == (1, ref.tree_actors(10))
    assert rt.counter("n_spawned") == 2046
    assert rt.counter("n_deadletter") == 0 and rt.counter("n_badmsg") == 0
    rt.gc()
    assert int(np.asarray(rt.state.alive).sum()) == 1
    assert rt.counter("n_collected") == 2046
    rt.release(root)
    rt.gc()
    assert not np.asarray(rt.state.alive).any()
    rt.stop()


def test_a_world_that_outgrows_its_rows_is_refused_loudly():
    """A tree of 2,047 actors does not fit 1,024 rows whatever is
    collected (everything a pass could free is live): the spawn is
    refused with SpawnCapacityError, never dropped."""
    rt = Runtime(RuntimeOptions(mailbox_cap=8, spill_cap=64, msg_words=2,
                                inject_slots=8, **QUIET))
    rt.declare(spreader.Spreader, 1024).start()
    root = rt.spawn(spreader.Spreader, parent=-1, depth=10, left=1)
    rt.send(root, spreader.Spreader.start, 0)
    with pytest.raises(SpawnCapacityError):
        rt.run()
    rt.stop()


def test_idle_and_garbage_rows_reserve_nothing():
    """Reservations are ranked over the rows that can dispatch this
    tick: a world whose alive rows (live parents waiting, garbage the
    collector has not reached) outnumber a third of its rows keeps
    spawning, and the pass is asked for by the rows, long before the
    cadence (`cd_interval` 128)."""
    world = _world(0, 4096, 6)
    rt = world.rt
    assert rt.run(max_steps=40) == 0
    now = world.counters()
    assert now["passes"] >= 2 and rt.steps_run == 40 < rt.opts.cd_interval
    assert rt.counter("n_spawned") == now["spawned"] > 2 * world.n
    _clean(world)
    rt.stop()


# ------------------------------------------------- the collector's trace

@actor
class Node:
    a: Ref
    b: Ref
    seen: I32

    @behaviour
    def link(self, st, other: Ref, n: I32):
        return {**st, "a": other, "seen": st["seen"] + n}

    @behaviour
    def ping(self, st, n: I32):
        return {**st, "seen": st["seen"] + n}


@actor
class Leaf:
    up: Ref

    @behaviour
    def poke(self, st, who: Ref):
        return {**st, "up": who}


def _random_world(seed, nodes, leaves, cap, full):
    """A started runtime whose device state is drawn at random: a graph
    with cycles over two cohorts (three Ref fields), some rows dead, some
    pinned, some muted; mailboxes at random depths (`full`: some rows
    hold all `cap` slots) whose messages carry Ref arguments or none;
    a spill that holds both kinds."""
    rng = np.random.default_rng(seed)
    rt = Runtime(RuntimeOptions(mailbox_cap=cap, spill_cap=32, msg_words=2,
                                inject_slots=8, **QUIET))
    rt.declare(Node, nodes).declare(Leaf, leaves).start()
    n = nodes + leaves
    st = rt.state

    def refs(size):
        return np.where(rng.random(size) < 0.6, rng.integers(0, n, size), -1)
    alive = rng.random(n) < 0.8
    pinned = alive & (rng.random(n) < 0.05)
    muted = alive & (rng.random(n) < 0.05)
    occ = np.where(alive & (rng.random(n) < 0.15),
                   rng.integers(1, 4, n), 0)
    if full:
        occ[rng.choice(np.nonzero(alive)[0], 3, replace=False)] = cap
    head = rng.integers(0, cap, n)
    gids = {t: [b.global_id for b in t.behaviour_defs] for t in (Node, Leaf)}
    buf, named = {}, []
    for atype, lo, hi in ((Node, 0, nodes), (Leaf, nodes, n)):
        rows = hi - lo
        table = np.zeros((cap, 3, rows), np.int32)
        table[:, 0] = rng.choice(gids[atype], (cap, rows))
        table[:, 1] = refs((cap, rows))
        table[:, 2] = refs((cap, rows))     # never a Ref: an I32 or unused
        buf[atype.__name__] = table
        slot = np.arange(cap)[:, None]
        valid = ((slot - head[lo:hi]) % cap) < occ[lo:hi]
        carries = np.isin(table[:, 0], [Node.link.global_id,
                                        Leaf.poke.global_id])
        named.append(table[:, 1][valid & carries])
    spill_tgt = np.where(rng.random(32) < 0.3, rng.integers(0, n, 32), -1)
    spill_words = np.zeros((3, 32), np.int32)
    spill_words[0] = rng.choice(gids[Node] + gids[Leaf], 32)
    spill_words[1] = refs(32)
    named += [spill_tgt, np.where(
        (spill_tgt >= 0) & np.isin(spill_words[0], [Node.link.global_id,
                                                    Leaf.poke.global_id]),
        spill_words[1], -1)]
    fields = {"Node": {"a": refs(nodes), "b": refs(nodes),
                       "seen": np.zeros(nodes, np.int64)},
              "Leaf": {"up": refs(leaves)}}
    as_dev = lambda x, like: jax.numpy.asarray(x, like.dtype)  # noqa: E731
    rt.state = rt._replace(
        alive=as_dev(alive, st.alive), pinned=as_dev(pinned, st.pinned),
        muted=as_dev(muted, st.muted), head=as_dev(head, st.head),
        tail=as_dev(head + occ, st.tail),
        buf={k: as_dev(v, st.buf[k]) for k, v in buf.items()},
        dspill_tgt=as_dev(spill_tgt, st.dspill_tgt),
        dspill_words=as_dev(spill_words, st.dspill_words),
        dspill_count=as_dev([(spill_tgt >= 0).sum()], st.dspill_count),
        type_state={t: {f: as_dev(v, st.type_state[t][f])
                        for f, v in cols.items()}
                    for t, cols in fields.items()})
    keeps = ref.reachable(
        alive, pinned | muted | (occ > 0),
        [(np.arange(nodes), fields["Node"]["a"]),
         (np.arange(nodes), fields["Node"]["b"]),
         (np.arange(nodes, n), fields["Leaf"]["up"])],
        np.concatenate([np.ravel(x) for x in named]))
    return rt, alive, keeps


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("nodes,leaves,cap,full",
                         [(48, 16, 8, False), (600, 200, 64, True)],
                         ids=["small-scattered", "cap64-full-merged"])
def test_trace_keeps_exactly_what_the_reference_reaches(seed, nodes, leaves,
                                                        cap, full):
    """On a random graph with cycles, Ref arguments in rings and in the
    spill, pinned and muted rows and (cap 64) full mailboxes, one pass
    leaves alive exactly the alive rows `reference_spreader.reachable`
    reaches: both of `marks_of`'s forms (the short lists' scatter, the
    long lists' sort and merge), the mailbox walk over occupied slots
    only, the hop by sorted edges."""
    rt, alive, keeps = _random_world(seed, nodes, leaves, cap, full)
    collected = rt.gc()
    after = np.asarray(rt.state.alive)
    assert np.array_equal(after, alive & keeps)
    assert collected == int((alive & ~keeps).sum()) > 0
    assert rt.run_loop_stats()["gc_runs"] == 1
    assert rt.run_loop_stats()["gc_iters"] >= 2
    rt.stop()


def test_marks_of_is_membership_in_both_forms():
    rng = np.random.default_rng(7)
    for size, n in ((5, 1000), (4000, 1000), (1000, 17)):
        ids = rng.integers(-3, n + 3, size)
        want = np.isin(np.arange(n), ids)
        assert np.array_equal(np.asarray(gc_mod.marks_of(
            jax.numpy.asarray(ids, jax.numpy.int32), n)), want), (size, n)


# --------------------------------------- what a spawn-free world compiles

def _lowered(rt):
    gated = engine.build_multi_step_gated(rt.program, rt.opts)
    return jax.jit(gated).lower(
        rt.state, *rt._empty_inject, jax.numpy.int32(4),
        jax.numpy.bool_(True), engine.zero_aux(rt.program))


def test_spawn_scopes_and_the_aux_leaves_exist_only_where_actors_spawn():
    """Row pressure rides the aux as a dict that is empty where nothing
    spawns: a spawn-free world's window has the parent's inputs and
    outputs, leaf for leaf, and none of the new scopes; the spreader's
    has `pony/spawn/{free,reserve,claim}` and three more aux leaves, and
    the collector's program names its roots, hop and sweep."""
    from ponyc_tpu.models import ubench
    rt, _ids = ubench.build(64, RuntimeOptions(
        mailbox_cap=4, batch=2, max_sends=1, msg_words=1, spill_cap=64,
        inject_slots=8, **QUIET), pings=2)
    plain = engine.zero_aux(rt.program)
    assert plain.spawn == plain.lists == plain.pool == {}
    assert plain == engine.zero_aux()
    assert len(jax.tree.leaves(plain)) == len(engine.StepAux._fields) - 3
    text = _lowered(rt).as_text(debug_info=True)
    for scope in ("spawn/free", "spawn/reserve", "spawn/claim"):
        assert f"{SCOPE_PREFIX}/{scope}/" not in text
    rt.stop()

    world = _world(0, 256, 3)
    rt = world.rt
    aux = engine.zero_aux(rt.program)
    assert sorted(aux.spawn) == ["low", "room", "spawned"]
    text = _lowered(rt).as_text(debug_info=True)
    for scope in ("spawn/free", "spawn/reserve", "spawn/claim"):
        assert f"{SCOPE_PREFIX}/{scope}/" in text, scope
    nl = rt.program.n_local
    gc_text = jax.jit(gc_mod.build_gc(rt.program, rt.opts)).lower(
        rt.state, np.zeros((nl,), bool), np.zeros((0,), bool)
    ).as_text(debug_info=True)
    for scope in ("gc_mark/roots", "gc_mark/hop", "gc_mark/sweep"):
        assert f"{SCOPE_PREFIX}/{scope}/" in gc_text, scope
    rt.stop()


def test_a_pass_the_rows_ask_for_is_one_pass_an_aux():
    """The window ends when the next tick's reservations would outrun
    the free rows; the run loop collects once and the next window's
    first tick runs: ticks are never repeated or skipped, whatever the
    window's length (one window of many ticks equals the same ticks one
    at a time), and the flight recorder holds each pass with the free
    rows it found."""
    from ponyc_tpu import flight
    a, b = _world(1, 1024, 4), _world(1, 1024, 4)
    assert a.rt.run(max_steps=36) == 0
    for _ in range(36):
        assert b.rt.run(max_steps=1) == 0
    for key in ("spawned", "collected", "passes", "hops", "free_rows_low"):
        assert a.counters()[key] == b.counters()[key], key
    assert np.array_equal(np.asarray(a.rt.state.alive),
                          np.asarray(b.rt.state.alive))
    assert a.counters()["passes"] >= 2
    events = [e for e in flight.latest().events if e["kind"] == "gc"]
    assert len(events) == b.counters()["passes"]
    assert all(e["free_before"] < b.n - b.roots and e["collected"] > 0
               for e in events)
    a.rt.stop()
    b.rt.stop()
