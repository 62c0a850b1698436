"""Task Bench's stencil with every dependency a payload in the device
blob pool (`benchmarks/worlds/taskbench.py`, the world of the cell
`taskbench-stencil.payload`) against its plain reference
(`benchmarks/reference_taskbench.py`), on the CPU at tiny widths; the
payload is never cut.

The deployment is the first whose messages carry `Blob` arguments: a
payload is allocated, filled, sent, read, checked and freed inside the
window, so the pool's reservation windows, its free list and the handle
checks of a message argument run on every tick. The tests hold the
world to the replay bit for bit, the pool to its books, the check to
being able to fail, an undersized pool to its sticky error, and the
five scopes below `dispatch/heap` to their names.
"""

import functools
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_taskbench as ref
from benchmarks.modes import throughput_payload as mode
from benchmarks.worlds import taskbench
from ponyc_tpu import BlobCapacityError
from ponyc_tpu.runtime import state

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WIDTHS = (8, 64, 256)
SEEDS = (3, 2**31 + 53, 977)
TICKS = (1, 2, 7, 20)
PAIRS = 16
HEAP_SCOPES = ("get", "set", "alloc", "free", "reserve")


def _files(**mix):
    with open(os.path.join(
            ROOT, "benchmarks/configs/taskbench-stencil.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmarks/traffic/payload.json")) as f:
        return cfg, {**json.load(f), **mix}


def _world(width, seed, **mix):
    cfg, traffic = _files(**mix)
    cfg["actors"] = width
    cfg["runtime_options"] = {**cfg["runtime_options"],
                              "compile_cache": "off", "tuning_cache": "off"}
    return taskbench.World(cfg, traffic, seed)


_WORLDS = []


@functools.lru_cache(maxsize=None)
def _history(width, seed):
    """One world a (width, seed), run to the last of TICKS: what it held
    and what its books said where each of TICKS ended, and after every
    tick the slots in use."""
    world = _world(width, seed)
    _WORLDS.append(world)
    rt, at, in_use = world.rt, {}, []
    for tick in range(1, TICKS[-1] + 1):
        code = rt.run(max_steps=1)
        in_use.append((rt.blobs_in_use,
                       rt.run_loop_stats()["pool"]["blobs_in_use"]))
        if tick in TICKS:
            at[tick] = {"code": code, "seen": world.observed(),
                        "check": world.check(),
                        "books": rt.run_loop_stats()["pool"]}
    return {"world": world, "at": at, "in_use": in_use}


@pytest.fixture(scope="module", autouse=True)
def _stop_the_worlds():
    yield
    while _WORLDS:
        _WORLDS.pop().rt.stop()
    _history.cache_clear()


# ---- the reference, by hand

def test_reference_on_a_case_worked_by_hand():
    """Width 4, one pair: timestep 0 writes (0, p); point 0 at timestep
    1 depends on points 3, 0, 1 and reads (0,3) (0,0) (0,1): acc = 4;
    at timestep 2 it reads (1,3) (1,0) (1,1): acc = 4 + 7 = 11."""
    assert ref.dependencies(0, 4).tolist() == [3, 0, 1]
    assert ref.dependencies([3], 4).tolist() == [[2, 3, 0]]
    assert ref.fill(5, 2, 3).tolist() == [5, 2, 5, 2, 5, 2]
    s = ref.Stencil(4, 1)
    assert s.live == 12 and s.step.tolist() == [1, 1, 1, 1]
    s.tick()
    assert s.acc.tolist() == [4, 3, 6, 5] and s.dispatched == 12
    s.tick()
    assert s.acc[0] == 11 and s.step.tolist() == [3, 3, 3, 3]
    assert not s.bad_inputs.any() and s.live == 12
    # a bounded run: timesteps 0 .. 2, the last writes nothing
    s = ref.Stencil(4, 1, steps=3).advance(5)
    assert (s.ticks, s.dispatched, s.live) == (5, 24, 0)
    assert s.step.tolist() == [3, 3, 3, 3]
    with pytest.raises(ValueError):
        ref.Stencil(2, 1)


@pytest.mark.parametrize("what", ["timestep", "point", "swapped", "short"])
def test_the_validator_says_which_pairs_are_wrong(what):
    """`execute_point`'s check on a tampered array: one word changed is
    one pair wrong, and only that one."""
    width, t, p = 16, 9, 0
    inputs = ref.fill(t - 1, ref.dependencies(p, width), PAIRS)
    assert inputs.shape == (3, 2 * PAIRS)
    assert not ref.wrong_pairs(t, p, inputs, width).any()
    bad = inputs.copy()
    if what == "timestep":
        bad[1, 6] += 1                       # input 1, pair 3, the timestep
        want = [(1, 3)]
    elif what == "point":
        bad[0, 31] = 14                      # input 0 is point 15's
        want = [(0, 15)]
    elif what == "swapped":
        bad[[0, 2]] = bad[[2, 0]]            # a foreign slot: every pair
        want = [(d, j) for d in (0, 2) for j in range(PAIRS)]
    else:
        bad[2, 16:] = 0                      # a torn buffer
        want = [(2, j) for j in range(8, PAIRS)]
    wrong = ref.wrong_pairs(t, p, bad, width)
    assert sorted(zip(*np.nonzero(wrong))) == want


# ---- the world against the replay

def test_the_world_is_the_configurations_shape():
    cfg, mix = _files()
    world = _history(64, SEEDS[0])["world"]
    point = world.Point
    assert (point.BATCH, point.MAX_SENDS, point.MAX_BLOBS,
            point.BLOB_DISPATCHES) == (3, 3, 3, 1)
    assert len(point.behaviour_defs) == 1
    assert world.rt.opts.msg_words == 3 and world.rt.opts.blob_words == 32
    assert world.rt.opts.blob_slots == 7 * 64 and world.live == 192
    assert mix["output_pairs"] == PAIRS and "steps" not in mix
    # every size the file states follows from its rules, under 2^20
    full = cfg["sizes"]
    assert taskbench.sizes(cfg["actors"]) == full
    assert full["blob_slots"] == 7 * 65536 >= 2 * full["live_payloads"]
    assert full["blob_slots"] == cfg["runtime_options"]["blob_slots"] < 2**20
    assert 6 * 174_762 < 2**20 <= 6 * 174_763
    for key in ("delivery", "batch", "max_sends"):
        assert key not in cfg["runtime_options"]
    with pytest.raises(ValueError, match="states"):
        taskbench.World({**cfg, "sizes": {**full, "blob_slots": 1}}, mix, 0)
    # the placement and the send slots are the seed's
    other = _history(64, SEEDS[1])["world"]
    assert not np.array_equal(world.point_of_row, other.point_of_row)
    assert len({tuple(o) for o in world.order}) == 6
    shape = world.tick_shape()
    assert shape == {"messages": 192, "dispatching_actors": 64.0,
                     "record_words": 4, "state_words": 9}


@pytest.mark.parametrize("ticks", TICKS)
@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("width", WIDTHS)
def test_every_point_is_the_replays_bit_for_bit(width, seed, ticks):
    got = _history(width, seed)["at"][ticks]
    want = ref.Stencil(width, PAIRS).advance(ticks)
    assert got["code"] == 0
    for column, values in want.observed().items():
        assert np.array_equal(got["seen"][column], values), column
    assert want.step.tolist() == [ticks + 1] * width
    assert not got["seen"]["bad_inputs"].any()
    assert got["check"]["points_off"] == 0
    assert all(got["check"]["checks"].values()), got["check"]["checks"]
    # the books the run loop keeps are the device's
    live = 3 * width
    assert got["books"] == {"allocs": live * (ticks + 1),
                            "frees": live * ticks, "blobs_in_use": live}
    assert got["check"]["books"] == got["books"]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("width", WIDTHS)
def test_the_pool_holds_three_payloads_a_point_after_every_tick(width, seed):
    assert _history(width, seed)["in_use"] \
        == [(3 * width, 3 * width)] * TICKS[-1]


def _arrival_order(world):
    """[width, 3]: for every point, its dependencies p - 1, p, p + 1 in
    the order their payloads arrive within a tick — by the send slot the
    sender gave this point, which is how the delivery list is laid."""
    w = world.width
    slot_of = np.argsort(world.order, axis=1)      # [point's row, d] -> slot
    rows = np.empty(w, np.int64)
    rows[world.point_of_row] = np.arange(w)
    keys = np.empty((w, 3), np.int64)
    for d in range(3):          # dependency d of q is point q - 1 + d,
        src = (np.arange(w) + d - 1) % w       # whose dependent 2 - d is q
        keys[:, d] = slot_of[rows[src], 2 - d]
    return np.argsort(keys, axis=1, kind="stable")


@pytest.mark.parametrize("order", taskbench.ORDERS)
def test_every_order_of_a_ticks_three_arrivals_gives_the_same_state(order):
    """The seeded send slots make the three arrivals of a point come in
    every one of the six orders somewhere in one world; the points of
    each order hold what the replay holds."""
    run = _history(256, SEEDS[0])
    world, got = run["world"], run["at"][TICKS[-1]]["seen"]
    points = np.flatnonzero((_arrival_order(world) == order).all(axis=1))
    assert len(points) >= 8, "the seed deals every order"
    want = ref.Stencil(256, PAIRS).advance(TICKS[-1]).observed()
    for column, values in want.items():
        assert np.array_equal(got[column][points], values[points]), column


def test_a_bounded_run_drains_the_pool():
    """`steps` 5: timesteps 0 .. 4, the last sends nothing, so `run()`
    reaches quiescence by itself with no payload left."""
    world = _world(64, SEEDS[2], steps=5)
    try:
        rt = world.rt
        assert rt.run() == 0
        assert rt.blobs_in_use == 0
        want = ref.Stencil(64, PAIRS, steps=5).advance(rt.steps_run)
        assert want.live == 0 and want.dispatched == 4 * 192
        assert rt.counter("n_processed") == want.dispatched
        found = world.check()
        assert found["points_off"] == 0 and all(found["checks"].values())
        assert world.observed()["step"].tolist() == [5] * 64
        assert rt.run_loop_stats()["pool"] == {
            "allocs": 4 * 192, "frees": 4 * 192, "blobs_in_use": 0}
        assert not any(world.errors().values())
    finally:
        world.rt.stop()


def test_a_tampered_payload_is_counted_and_fails_the_check():
    """One word of one live payload changed between two windows: the
    point that reads it counts one bad input, `acc` carries the change,
    and the mode's comparison fails on exactly that point."""
    world = _world(8, SEEDS[1])
    try:
        rt = world.rt
        assert rt.run(max_steps=2) == 0
        assert world.check()["points_off"] == 0
        st = rt.state
        slot = int(np.flatnonzero(np.asarray(st.blob_used))[5])
        word = state.pool_index(rt.opts.blob_slots, 7, slot)
        owner = int(np.asarray(st.blob_data)[
            state.pool_index(rt.opts.blob_slots, 1, slot)])
        rt.state = rt._replace(
            blob_data=st.blob_data.at[word].add(jnp.int32(1000)))
        assert rt.run(max_steps=1) == 0
        seen = world.observed()
        assert seen["bad_inputs"].sum() == 1
        reader = int(np.flatnonzero(seen["bad_inputs"])[0])
        assert owner in ref.dependencies(reader, 8).tolist()
        found = world.check()
        assert found["points_off"] == 1
        assert found["off"] == {"step": 0, "acc": 1, "seen": 0, "mask": 0,
                                "bad_inputs": 1}
        assert not found["checks"]["every_point_is_the_reference"]
        # the pool's books do not see it: the words are the payload's
        assert found["checks"]["pool_holds_the_live_payloads"]
        # ... and the mode counts it as failed
        plan = {"first": found, "reference_ok": False, "codes": [0], "k": 1}
        win = mode.window(world, {"k": 1, "counter":
                                  rt.counter("n_processed")}, 0.0)
        out = mode.finish(world, plan, win, None)
        assert out["failed"] >= 2 and not all(out["checks"].values())
    finally:
        world.rt.stop()


@pytest.mark.parametrize("slots_short", [0, 1])
def test_the_pool_must_hold_the_live_payloads_and_one_window(slots_short):
    """Live payloads plus one reservation window is what the pool must
    hold (a tick's allocations come out of the slots free at its start):
    6 x width runs; one slot less and the window's last lane finds the
    free list short — the run ends in BlobCapacityError, not in
    silence."""
    cfg, mix = _files()
    cfg["actors"] = 8
    cfg["runtime_options"] = {**cfg["runtime_options"],
                              "compile_cache": "off", "tuning_cache": "off"}
    real = taskbench.sizes
    taskbench.sizes = lambda width: {**real(width),
                                     "blob_slots": 6 * width - slots_short}
    try:
        world = taskbench.World(cfg, mix, SEEDS[0])
    finally:
        taskbench.sizes = real
    try:
        assert world.rt.opts.blob_slots == 48 - slots_short
        if slots_short:
            with pytest.raises(BlobCapacityError):
                world.rt.run(max_steps=4)
        else:
            assert world.rt.run(max_steps=4) == 0
            assert all(world.check()["checks"].values())
    finally:
        world.rt.stop()


# ---- the scopes and the counters

def test_the_five_heap_scopes_name_the_window_and_heap_sums_them():
    from benchmarks import phase_trace
    for scope in HEAP_SCOPES:
        assert f"dispatch/heap/{scope}" in state.STEP_SCOPES
    world = _history(64, SEEDS[0])["world"]
    rows = world.rt.window_symbols()["window"]
    named = {r["scope"] for r in rows}
    assert named >= {f"dispatch/heap/{s}" for s in HEAP_SCOPES}
    kinds = {(r["scope"], r["kind"]) for r in rows}
    for scope, kind in (("get", "gather"), ("set", "scatter"),
                        ("set", "sort"), ("alloc", "scatter"),
                        ("free", "scatter"), ("reserve", "gather")):
        assert (f"dispatch/heap/{scope}", kind) in kinds, (scope, kind)
    # what a reader sums under `dispatch/heap` is the five and the bare
    # scope, and all of it lies under `dispatch`
    reduced = {"scoped": True, "phases": {
        **{f"dispatch/heap/{s}": {"s": 1.0} for s in HEAP_SCOPES},
        "dispatch/heap": {"s": 0.5}, "dispatch": {"s": 2.0},
        "delivery": {"s": 4.0}}}
    assert phase_trace.under(reduced, "dispatch/heap") == 5.5
    assert phase_trace.under(reduced, "dispatch") == 7.5
    assert phase_trace.scope_of(
        "jit(f)/while/body/pony/dispatch/cohort/Point/while/body/pony/"
        "dispatch/heap/set/scatter") == "dispatch/heap/set"


def test_the_pools_books_ride_the_windows_own_fetch():
    """`run_loop_stats()["pool"]` is host arithmetic on the aux the
    retire already fetched; a program whose pool no window can move
    (GUPS's table: handles in state fields, set at build time) has no
    such leaf and its window stays the program it was."""
    from ponyc_tpu import I32, Blob, Runtime, RuntimeOptions, actor, behaviour
    from ponyc_tpu.runtime import engine
    world = _history(8, SEEDS[0])["world"]
    assert state.counts_pool(world.rt.program)
    assert set(engine.zero_aux(world.rt.program).pool) == {"alloc", "free"}
    before = dict(world.rt.run_loop_stats()["phase_n"])
    books = world.rt.run_loop_stats()["pool"]
    assert books["blobs_in_use"] == 24
    after = world.rt.run_loop_stats()["phase_n"]
    assert after == before, "no read, no fetch: host integers"

    @actor
    class Keeper:
        table: Blob
        n: I32

        @behaviour
        def touch(self, st, i: I32):
            self.blob_set(st["table"], i, self.blob_get(st["table"], i) + 1)
            return {**st, "n": st["n"] + 1}

    rt = Runtime(RuntimeOptions(mailbox_cap=4, batch=2, msg_words=1,
                                blob_slots=4, blob_words=4,
                                compile_cache="off", tuning_cache="off"))
    rt.declare(Keeper, 4).start()
    try:
        assert not state.counts_pool(rt.program)
        assert engine.zero_aux(rt.program).pool == {}
        ids = rt.spawn_many(Keeper, 4, table=rt.blob_store_many(4))
        rt.send(int(ids[0]), Keeper.touch, 2)
        assert rt.run() == 0
        assert rt.run_loop_stats()["pool"] is None
        assert rt.blobs_in_use == 4
    finally:
        rt.stop()
