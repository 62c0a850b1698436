"""The fan-in under a Zipf ON A MESH (`benchmarks/worlds/fanin_mesh.py`,
the world of the cell `fanin-zipf-mesh4.crossing`) against the protocol
written down with the layout in it (`benchmarks/reference_fanin_mesh.py`),
on four virtual CPU devices at small sizes.

What a mesh adds to the backpressure chain: a receiver's own shard mutes
the senders that live on it (`delivery.deliver`, step 5); every other
sender mutes at routing, by the mesh-wide hot word, in the first tick in
which it sends to a receiver that the tick before left overloaded
(`route._route_spill`), and is released when that receiver has recovered
(`mute.unmute_pass`). Held here: the trace tick by tick on every actor,
the spill's bound on every shard at every tick, conservation, nobody
stranded muted, the route's counters — and the world that used to end
in `SpillOverflowError`.
"""

import json
import os

import numpy as np
import pytest

from benchmarks import reference_fanin as ref
from benchmarks import reference_fanin_mesh as ref_mesh
from benchmarks.worlds import fanin, fanin_mesh
from test_fanin_zipf import _conserved, _same

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHARDS = 4
TICKS = 200
OFF = {"compile_cache": "off", "tuning_cache": "off"}


def _files(config, mix):
    def load(*parts):
        with open(os.path.join(ROOT, "benchmarks", *parts)) as f:
            return json.load(f)
    return load("configs", config + ".json"), load("traffic", mix + ".json")


def _world(actors, seed, *, traffic=(), **options):
    cfg, mix = _files("fanin-zipf-mesh4", "crossing")
    cfg["actors"] = actors
    cfg["runtime_options"] = {**cfg["runtime_options"], **OFF, **options}
    return fanin_mesh.build(cfg, {**mix, **dict(traffic)}, seed)


# (seed 7 at 4,096 actors is the world of ISSUE 47's Motivation)
@pytest.mark.parametrize("actors, seed", [(4096, 7), (2048, 3)],
                         ids=["512+3584-seed7", "256+1792-seed3"])
def test_the_mesh_follows_the_protocol_tick_by_tick(actors, seed):
    world = _world(actors, seed)
    rt, plain = world.rt, world._ticks
    assert rt.opts.spill_cap >= world.spill_bound.max()
    crossing = world.prod_ids // rt.program.n_local \
        != world.agg_ids[world.out] // rt.program.n_local
    assert 0.7 < crossing.mean() < 0.8          # 3 of 4 edges cross
    remote_muted = False
    for tick in range(1, TICKS + 1):
        assert rt.run(max_steps=1) == 0
        seen = world.observed()
        _same(seen, world.reference(tick), tick)
        # the spill's bound: B items a producer wired to the shard
        assert (world.spill_by_shard() <= world.spill_bound).all(), tick
        assert rt.counter("rspill_count") == 0
        remote_muted = remote_muted or bool((seen["muted"] & crossing).any())
        # B: two items a remote producer outside a mailbox, one a local
        assert (np.bincount(plain.spill_snd, minlength=world.p)
                <= 1 + crossing).all(), tick
        if tick % 40 == 0:
            _conserved(world)
            for name, want in plain.route_counters().items():
                assert rt.counter(name) == want, (tick, name)
    assert remote_muted
    assert rt.counter("n_rejected") == plain.n_rejected > 0
    assert rt.counter("n_mutes") == plain.n_mutes > 0
    assert rt.counter("n_remote_mutes") == plain.n_remote_mutes > 0
    assert rt.counter("n_badmsg") == rt.counter("n_deadletter") == 0
    # every tick but the first found someone overloaded; every shard-tick
    # delivered over the short list
    assert rt.counter("n_route_pressure") == SHARDS * (TICKS - 1)
    assert rt.counter("n_unpacked") == SHARDS * TICKS
    assert 0 < plain.spill_peak <= world.spill_bound.max()
    rt.stop()


def test_the_world_that_overflowed_runs_on():
    """ISSUE 47's Motivation: `worlds/fanin.py` as it is, `mesh_shards`
    4, 4,096 actors, seed 7. Three of four producers of a hot aggregator
    live on another shard; while only the receiver's own shard muted,
    the four spills read [34, 153, 619, 83] after the first tick,
    [178, 356, 1195, 218] after the second, grew by 580 entries a tick
    and `run()` raised SpillOverflowError at step 7. The first two
    ticks are the same still (the remote mute lands a tick late)."""
    cfg, mix = _files("fanin-zipf", "steady")
    cfg["actors"] = 4096
    cfg["runtime_options"] = {**cfg["runtime_options"], **OFF,
                              "mesh_shards": SHARDS}
    world = fanin.build(cfg, mix, 7)
    rt = world.rt
    assert rt.opts.spill_cap == 4096
    spills, muted = [], []
    for _ in range(60):
        assert rt.run(max_steps=1) == 0
        spills.append(np.asarray(rt.state.dspill_count).tolist())
        muted.append(int(np.asarray(rt.state.muted).sum()))
    assert spills[:2] == [[34, 153, 619, 83], [178, 356, 1195, 218]]
    assert muted[0] == 403
    # the second tick's is the fullest any spill gets: the mute that
    # crosses the shards arrives, and the spills drain
    assert max(max(s) for s in spills) == 1195
    assert min(muted[8:]) > 1800               # one shard holds ~2,100
    assert rt.counter("n_remote_mutes") > 0
    _conserved(world)
    rt.stop()


@pytest.mark.parametrize("seed", [0, 1])
def test_a_finite_mix_ends_with_nobody_muted(seed):
    """`hops` items a producer, then nothing: every item is counted
    once and nobody stays muted behind a drained aggregator, on
    whatever shard it lives."""
    hops = 24
    world = _world(2048, seed, traffic={"hops": hops})
    rt = world.rt
    saw_remote = 0
    for _ in range(6):
        rt.run(max_steps=8)
        saw_remote = max(saw_remote, rt.counter("n_remote_mutes"))
        kept = world.conservation()
        assert kept["deficit"] == 0
        assert kept["checks"]["conservation_every_aggregator"], kept
        assert kept["checks"]["muted_only_behind_work"], kept
    assert rt.run() == 0                        # to quiescence
    seen = world.observed()
    assert (seen["sent"] == hops).all() and not seen["muted"].any()
    assert not np.asarray(rt.state.muted).any()
    assert seen["queued"].sum() == 0 and seen["spilled"].sum() == 0
    assert np.array_equal(seen["total"],
                          hops * np.bincount(world.out, minlength=world.a))
    assert saw_remote > 0 and rt.counter("n_rejected") > 0
    assert rt.counter("n_badmsg") == rt.counter("n_deadletter") == 0
    rt.stop()


def test_on_one_shard_the_mesh_reference_is_the_one_chip_protocol():
    """The one-chip fan-in's trace is the parent's, bit for bit: the
    mesh world's file on `mesh_shards` 1 follows `reference_fanin.Ticks`
    (the one-shard protocol, untouched), and the mesh reference given
    one shard is that protocol too — rule 3 never fires where every
    producer lives on its aggregator's shard."""
    world = _world(2048, 5, mesh_shards=1)
    rt = world.rt
    one = ref.Ticks(world.out, world.a, **world.protocol)
    for tick in range(1, 49):
        assert rt.run(max_steps=1) == 0
        want = one.advance(1).observed()
        _same(world.observed(), want, tick)
        _same(world.reference(tick), want, tick)
    mesh_ref = world._ticks
    assert (mesh_ref.n_rejected, mesh_ref.n_mutes) == (
        one.n_rejected, one.n_mutes) == (
        rt.counter("n_rejected"), rt.counter("n_mutes"))
    assert mesh_ref.n_remote_mutes == rt.counter("n_remote_mutes") == 0
    rt.stop()


def test_the_remote_mutes_rule_by_hand():
    """Two shards of four ids; one aggregator (id 3, shard 0) with a
    ring of ONE slot, fed by a producer on its own shard (id 0) and two
    on the other (ids 4, 5); overloaded above 1, calm at 0."""
    t = ref_mesh.Ticks(np.zeros(3, np.int64), np.array([0, 4, 5]),
                       np.array([3]), 4, mailbox_cap=1, batch=1,
                       overload_occ=1, unmute_occ=0)
    assert t.shards == 2 and list(t.crosses) == [False, True, True]
    t.tick()
    # all three sent; id 0's item took the slot, the others' were
    # rejected. The aggregator's shard mutes the rejected senders IT
    # holds: none; id 0's item was accepted and one message is not over
    # the line: nobody is muted yet
    assert list(t.sent) == [1, 1, 1] and not t.muted.any()
    assert list(t.spill_snd) == [1, 2]
    assert (t.n_routed, t.n_routed_remote, t.n_lookups) == (6, 2, 0)
    t.tick()
    # the tick found spilled items waiting: the hot word is set, all
    # three run once more and are muted at routing (rule 3). The slot
    # freed by the drain takes the oldest spilled item; the last
    # producer now has TWO items outside a mailbox, the local one ONE
    assert list(t.sent) == [2, 2, 2] and t.muted.all()
    assert (t.n_remote_mutes, t.n_mutes, t.n_lookups) == (2, 3, 2)
    assert list(t.spill_snd) == [2, 0, 1, 2] and t.spill_peak == 4
    for tick in range(3, 40):
        before = t.sent.copy()
        t.tick()
        # B: two items a remote producer outside a mailbox, one a local
        assert (np.bincount(t.spill_snd, minlength=3)
                <= 1 + t.crosses).all(), tick
        # a muted producer does not run while anything is spilled for
        # its aggregator
        if tick <= 6:
            assert t.muted.all() and (t.sent == before).all(), tick
    # the spill drained one item a tick (ticks 3-6), the ring at tick 7,
    # everyone was released at tick 8 and the round began again
    assert t.sent.min() > 2 and t.n_mutes > 3
    assert (t.total[0] + (t.tail - t.head)[0] + len(t.spill_tgt)
            == t.sent.sum())


def test_spill_capacity_by_hand():
    cap = ref_mesh.spill_capacity
    # the floor: B x twice a shard's even share, the next power of two
    assert cap(2, 3670016, 4, 0) == cap(2, 3670016, 4, 1227707) == 1 << 22
    assert cap(2, 3670016, 4, 998654) == 1 << 22       # seed 1: no 2**21
    assert cap(2, 3670016, 4, 2097153) == 1 << 23
    assert cap(1, 917504, 1, 917504) == 1 << 21        # floor 2 x all
    wired = ref_mesh.wired_to_shards(np.array([0, 1, 1, 5]),
                                     np.arange(6) % 2, 2)
    assert list(wired) == [1, 3]


def test_a_quiet_mesh_pays_nothing():
    """ubench on a mesh: nobody is overloaded, so world bit 3 stays
    clear, nothing is gathered or looked up and nobody mutes."""
    from benchmarks.worlds import ubench_mesh
    cfg, mix = _files("ubench-4m-mesh4", "remote")
    cfg["actors"] = 2048
    cfg["runtime_options"] = {**cfg["runtime_options"], **OFF}
    world = ubench_mesh.build(cfg, mix, 3)
    rt = world.rt
    for _ in range(24):
        assert rt.run(max_steps=1) == 0
        assert not np.asarray(rt.state.world_bits).any()
    for name in ("n_route_pressure", "n_remote_mutes", "n_mutes",
                 "n_rejected", "rspill_count"):
        assert rt.counter(name) == 0, name
    assert rt.counter("n_unpacked") == SHARDS * 24
    rt.stop()
