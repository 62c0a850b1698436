"""Cross-shard pressure paths on the 8-virtual-device mesh.

≙ the reference's backpressure invariants under contention
(mute/unmute walks, scheduler.c:1478-1635; bounded queues are the
divergence — overflow spills are finite and their exhaustion is fatal).
These tests force the paths a quiet mesh never takes: all_to_all bucket
overflow → route spill → sender mute → retry → unmute; receiver-side
overflow spill across shards; and the spill-overflow abort.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ponyc_tpu import I32, Ref, Runtime, RuntimeOptions, actor, behaviour
from ponyc_tpu.runtime import mute
from ponyc_tpu.runtime.runtime import SpillOverflowError


@actor
class Burst:
    """Sends one message per tick to a fixed target, `left` times."""
    out: Ref
    left: I32
    MAX_SENDS = 2

    @behaviour
    def go(self, st, _: I32):
        alive = st["left"] > 0
        self.send(st["out"], Sink.recv, 1, when=alive)
        self.send(self.actor_id, Burst.go, 0, when=st["left"] > 1)
        return {**st, "left": st["left"] - 1}


@actor
class Sink:
    got: I32

    @behaviour
    def recv(self, st, v: I32):
        return {**st, "got": st["got"] + v}


def _run_pressure(opts, n_src=48, items=4, go=True):
    """n_src senders spread over all shards flood ONE sink on shard 0
    (`go=False`: spawned, none started)."""
    rt = Runtime(opts)
    rt.declare(Burst, n_src).declare(Sink, 4)
    rt.start()
    sink = rt.spawn(Sink)
    srcs = rt.spawn_many(Burst, n_src, out=int(sink), left=items)
    for s in srcs if go else ():
        rt.send(int(s), Burst.go, 0)
    return rt, sink, srcs


def test_route_bucket_overflow_spills_mutes_and_recovers():
    # Worst-case fan-in across the mesh: every shard's senders target one
    # shard; per-tick emissions exceed the all_to_all bucket, so messages
    # park in route-spill and their senders mute (route._route pressure
    # branch). Everything must still arrive exactly once.
    opts = RuntimeOptions(mailbox_cap=4, batch=1, max_sends=2, msg_words=2,
                          mesh_shards=4, spill_cap=256, inject_slots=64,
                          quiesce_interval=1, route_bucket=8)
    rt, sink, srcs = _run_pressure(opts, n_src=48, items=4)
    saw_rspill = False
    saw_muted = False
    for _ in range(400):
        rt.run(max_steps=1)
        saw_rspill = saw_rspill or rt.counter("rspill_count") > 0
        saw_muted = saw_muted or bool(np.asarray(rt.state.muted).any())
        if rt.state_of(int(sink))["got"] == 48 * 4:
            break
    assert rt.state_of(int(sink))["got"] == 48 * 4
    assert saw_rspill, "bucket overflow never engaged the route spill"
    assert saw_muted, "pressure never muted a sender"
    assert rt.counter("n_mutes") > 0
    # Quiescent end state: every sender released again (unmute pass).
    rt.run(max_steps=50)
    assert not np.asarray(rt.state.muted).any()
    assert rt.counter("rspill_count") == 0


def test_receiver_spill_crosses_shards_and_drains():
    # Bucket large enough (big spill_cap ⇒ big bucket) that routing
    # passes everything through; the RECEIVER mailbox (cap 4) overflows
    # instead, exercising the delivery spill + mute on a mesh.
    opts = RuntimeOptions(mailbox_cap=4, batch=2, max_sends=2, msg_words=2,
                          mesh_shards=4, spill_cap=2048, inject_slots=64)
    rt, sink, srcs = _run_pressure(opts, n_src=32, items=4)
    saw_dspill = False
    for _ in range(400):
        rt.run(max_steps=1)
        saw_dspill = saw_dspill or rt.counter("dspill_count") > 0
        if rt.state_of(int(sink))["got"] == 32 * 4:
            break
    assert rt.state_of(int(sink))["got"] == 32 * 4
    assert saw_dspill, "receiver overflow never engaged the delivery spill"
    rt.run(max_steps=50)
    assert not np.asarray(rt.state.muted).any()
    assert rt.counter("dspill_count") == 0


def test_spill_overflow_aborts_on_mesh():
    # spill_cap far below the one-tick reject volume: the bounded spill
    # exhausts and the runtime must fail loudly (SpillOverflowError),
    # not drop messages.
    opts = RuntimeOptions(mailbox_cap=4, batch=1, max_sends=2, msg_words=2,
                          mesh_shards=4, spill_cap=4, inject_slots=256,
                          overload_threshold=10.0)  # mute never triggers
    rt = Runtime(opts)
    rt.declare(Burst, 64).declare(Sink, 4)
    rt.start()
    sink = rt.spawn(Sink)
    srcs = rt.spawn_many(Burst, 64, out=int(sink), left=8)
    for s in srcs:
        rt.send(int(s), Burst.go, 0)
    with pytest.raises(SpillOverflowError):
        rt.run(max_steps=200)


def test_mesh_serialise_roundtrip_under_pressure(tmp_path):
    # Snapshot mid-pressure (spills populated, senders muted), restore
    # into a fresh runtime, and finish: nothing lost, nothing doubled.
    from ponyc_tpu import serialise

    opts = RuntimeOptions(mailbox_cap=4, batch=1, max_sends=2, msg_words=2,
                          mesh_shards=4, spill_cap=256, inject_slots=64)
    rt, sink, srcs = _run_pressure(opts, n_src=48, items=4)
    for _ in range(6):
        rt.run(max_steps=1)
    got_mid = rt.state_of(int(sink))["got"]
    assert got_mid < 48 * 4
    path = str(tmp_path / "mesh_pressure.npz")
    serialise.save(rt, path)

    rt2 = Runtime(opts)
    rt2.declare(Burst, 48).declare(Sink, 4)
    rt2.start()
    serialise.restore(rt2, path)
    assert rt2.state_of(int(sink))["got"] == got_mid
    rt2.run(max_steps=400)
    assert rt2.state_of(int(sink))["got"] == 48 * 4
    assert not np.asarray(rt2.state.muted).any()


def test_programmatic_backpressure_on_mesh():
    """apply_backpressure on a sharded world: senders on EVERY shard mute
    when their sends target the pressured (remote) receiver, and release
    after the host clears it (the pressured column shards with the actor
    axis). mailbox_cap is large enough that occupancy muting
    (overload_occ) can never fire — any mute is the programmatic path."""
    opts = RuntimeOptions(mailbox_cap=64, batch=4, max_sends=2,
                          msg_words=2, mesh_shards=4, spill_cap=512,
                          inject_slots=64, quiesce_interval=1)
    rt, sink, srcs = _run_pressure(opts, n_src=16, items=40)
    inj = rt._drain_inject()
    st, aux = rt._step(rt.state, *inj)
    inj = rt._empty_inject
    st, aux = rt._step(st, *inj)
    rt.state = st
    assert not np.asarray(st.muted).any(), "no pressure yet"

    rt.apply_backpressure([int(sink)])
    st = rt.state
    for _ in range(3):
        st, aux = rt._step(st, *inj)
    rt.state = st
    muted = np.asarray(st.muted)
    occ = int(np.asarray(st.tail - st.head)[int(sink)])
    assert muted.any(), "pressured receiver must mute senders"
    assert occ <= rt.opts.overload_occ, \
        "mute was pressure-driven, not occupancy-driven"
    # The pressure signal must cross the mesh: some muted sender lives on
    # a different shard than the sink (ids are shard-major: shard = id //
    # n_local).
    n_local = rt.program.n_local
    sink_shard = int(sink) // n_local
    muted_shards = set(int(i) // n_local for i in np.nonzero(muted)[0])
    assert muted_shards - {sink_shard}, \
        f"only shard {sink_shard} muted: {muted_shards}"

    rt.release_backpressure([int(sink)])
    assert rt.run(max_steps=4000) == 0
    assert rt.state_of(int(sink))["got"] == 16 * 40
    assert not np.asarray(rt.state.muted).any()


# --- the pressure lookup runs only behind world bit 0 -------------------
# `route._route_spill` looks its sorted entries' targets up in the
# mesh-wide hot word only on a tick whose world bit 0 or 3
# (`mute.world`'s `hot_anywhere`: someone declares pressure or is
# overloaded; nobody is overloaded in this world) is set; on every other
# tick the table is zeros and so is the answer. Held against the world in which
# the bit always reads set: the lookup on every tick, as it was.

SOURCES, ITEMS = 16, 12
QUIET, PRESSURED, AFTER = 2, 3, 16      # ticks before / under / after
PHASE = QUIET + PRESSURED + AFTER
TARGETS = ("remote", "local")           # the phases of one world, in turn


def _leaves(rt, but=("n_route_pressure", "n_route_prefix")):
    """Every state leaf by path (muted, mute_refs, the route spill, the
    actors' own counts, ...) `but` the counter of the choice itself and
    the one that counts a part of the ticks it counts (PR 50: those
    whose spill read the entries' prefix alone)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(rt.state)
    return {jax.tree_util.keystr(path): np.asarray(leaf)
            for path, leaf in flat
            if not any(name in jax.tree_util.keystr(path) for name in but)}


@functools.lru_cache(maxsize=None)
def _declared_twice(shards, always_look_up):
    """One meshed world ticked twice through quiet -> the sink declares
    pressure -> its senders mute at routing time -> release -> unmute:
    first with four senders none of which lives on the sink's shard
    ("remote"), then with four that all do ("local"). Four senders of
    one item a tick are what the sink drains (batch 4), so its mailbox
    never nears the overload line. With `always_look_up`, `mute.world`'s
    `hot_anywhere` always reads set: `_route_spill` gathers on every tick.
    Returns per tick (world bit 0 as the tick found it, every state
    leaf after it) and what the tests ask of the world."""
    opts = RuntimeOptions(mailbox_cap=64, batch=4, max_sends=2,
                          msg_words=2, mesh_shards=shards, spill_cap=512,
                          inject_slots=64, quiesce_interval=1)
    with pytest.MonkeyPatch.context() as patched:
        if always_look_up:
            real = mute.world
            patched.setattr(mute, "world", lambda k, st: real(
                k, st)._replace(hot_anywhere=jnp.bool_(True)))
        rt, sink, srcs = _run_pressure(opts, SOURCES, ITEMS, go=False)
        sink, n_local = int(sink), rt.program.n_local
        seen = []

        def tick(inject=None):
            bit = int(np.asarray(rt.state.world_bits)[0]) & 1
            rt.state, _aux = rt._step(
                rt.state, *(inject or rt._empty_inject))
            seen.append((bit, _leaves(rt)))

        for where in TARGETS:
            for s in [s for s in srcs if (int(s) // n_local == sink // n_local)
                      == (where == "local")][:4]:
                rt.send(int(s), Burst.go, 0)
            tick(rt._drain_inject())
            for _ in range(QUIET - 1):
                tick()
            rt.apply_backpressure([sink])
            for _ in range(PRESSURED):
                tick()
            rt.release_backpressure([sink])
            for _ in range(AFTER):
                tick()
        world = dict(sink=sink, n_local=n_local,
                     overload_occ=rt.opts.overload_occ,
                     looked_up=rt.counter("n_route_pressure"))
        rt.stop()
    return seen, world


@pytest.mark.parametrize("where", TARGETS)
@pytest.mark.parametrize("shards", [2, 4])
def test_the_gated_lookup_is_the_lookup_on_every_tick(shards, where):
    seen, world = _declared_twice(shards, False)
    want, forced = _declared_twice(shards, True)
    assert len(seen) == len(want) == PHASE * len(TARGETS)
    first = PHASE * TARGETS.index(where)
    mine = seen[first:first + PHASE]
    for t, ((bit, got), (_bit, ref)) in enumerate(
            zip(mine, want[first:first + PHASE])):
        assert got.keys() == ref.keys()
        bad = [k for k in got if not np.array_equal(got[k], ref[k])]
        assert not bad, (where, t, bit, bad[:6])
    # the bit is set from the declaration to the tick after the release
    # (whose vote clears it), and on no other tick
    bits = [bit for bit, _ in mine]
    assert bits == ([0] * QUIET + [1] * (PRESSURED + 1)
                    + [0] * (AFTER - 1)), bits
    # the script did what it says: the four senders muted under
    # pressure, on the shards it says, while the sink's mailbox was far
    # from its overload line; everyone was released and every item of
    # this phase and the one before arrived once
    under = mine[QUIET + PRESSURED - 1][1]
    muted = np.flatnonzero(under[".muted"]) // world["n_local"]
    on_sinks = muted == world["sink"] // world["n_local"]
    assert muted.size == 4
    assert on_sinks.all() if where == "local" else not on_sinks.any()
    assert (int((under[".tail"] - under[".head"])[world["sink"]])
            <= world["overload_occ"])
    assert not mine[-1][1][".muted"].any()
    assert (int(mine[-1][1][".type_state['Sink']['got']"].sum())
            == 4 * ITEMS * (1 + TARGETS.index(where)))
    # and the counter: a shard-tick for every tick whose bit was set
    assert world["looked_up"] == shards * sum(bit for bit, _ in seen)
    assert forced["looked_up"] == shards * len(want)


@pytest.mark.parametrize("leaf", ["n_route_pressure", "n_remote_mutes",
                                  "n_route_prefix"])
@pytest.mark.parametrize("target", ["same-layout", "relayout"])
def test_a_snapshot_from_before_the_counter_restores_with_it_at_zero(
        tmp_path, target, leaf):
    """`n_route_pressure` (PR 46), `n_remote_mutes` (PR 47) and
    `n_route_prefix` (PR 50) are state leaves newer than snapshots in
    the wild: one without the leaf restores, the other route counters
    carried. (A route spill of 1,024: sorted entries enough for the
    spill's second length, a quarter of them.)"""
    from ponyc_tpu import serialise

    opts = RuntimeOptions(mailbox_cap=8, batch=2, max_sends=2, msg_words=2,
                          mesh_shards=2, spill_cap=1024, inject_slots=64)
    rt, sink, _srcs = _run_pressure(opts, n_src=8, items=4)
    rt.apply_backpressure([int(sink)])
    rt.run(max_steps=3)
    assert rt.counter(leaf) > 0
    header, arrays = serialise.capture(rt)
    del arrays[f"st.route_counts.{leaf}"]
    path = str(tmp_path / "older.npz")
    serialise.write_snapshot(header, arrays, path)

    if target == "relayout":
        opts = dataclasses.replace(opts, mailbox_cap=16)
    rt2 = Runtime(opts)
    rt2.declare(Burst, 8).declare(Sink, 4)
    rt2.start()
    serialise.restore(rt2, path)
    assert rt2.counter(leaf) == 0
    assert rt2.counter("n_routed") == rt.counter("n_routed") > 0
    rt.stop()
    rt2.stop()


def test_an_overloaded_receiver_mutes_senders_on_other_shards():
    """No declared pressure, no full link: the sink's mailbox (cap 4)
    overflows, its own shard mutes the senders it holds in that tick,
    and the senders on the other shards are muted at routing in the
    next tick they send, by the hot word, with the sink as their ref —
    and everyone is released once the sink has drained."""
    opts = RuntimeOptions(mailbox_cap=4, batch=1, max_sends=2, msg_words=2,
                          mesh_shards=4, spill_cap=2048, inject_slots=64,
                          quiesce_interval=1)
    rt, sink, srcs = _run_pressure(opts, n_src=32, items=6)
    sink, nl = int(sink), rt.program.n_local
    far = np.asarray([int(s) for s in srcs if int(s) // nl != sink // nl])
    saw = 0
    for _ in range(12):
        rt.run(max_steps=1)
        muted = np.asarray(rt.state.muted)[far]
        refs = np.asarray(rt.state.mute_refs)[:, far]
        assert ((refs == sink).any(axis=0) == muted).all()
        saw = max(saw, int(muted.sum()))
    assert saw > len(far) // 2 and rt.counter("n_remote_mutes") >= saw
    assert rt.counter("rspill_count") == 0          # no link was full
    # (some ticks before the sink's overload there is nothing to look up)
    assert 0 < rt.counter("n_route_pressure") <= 4 * rt.steps_run
    assert rt.run(max_steps=2000) == 0
    assert rt.state_of(sink)["got"] == 32 * 6
    assert not np.asarray(rt.state.muted).any()
    # the tick after nobody is overloaded any more looks nothing up
    looked = rt.counter("n_route_pressure")
    rt.run(max_steps=4)
    assert rt.counter("n_route_pressure") == looked
    rt.stop()
