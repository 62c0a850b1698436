"""The fan-in under a Zipf (`benchmarks/worlds/fanin.py`, the world of
the cell `fanin-zipf.steady`) against its plain references
(`benchmarks/reference_fanin.py`), on the CPU at small sizes.

The deployment lives in the backpressure chain: reject -> spill ->
mute -> unmute (`delivery.py`'s pressure branch, `mute.py`'s unmute
pass). One shard is held to the protocol tick by tick, on every actor;
conservation (nothing lost, nothing duplicated) is checked on the way.
A mesh mutes differently — a receiver's rejection mutes the senders
resident on its shard at once and the others a tick later, at routing
— so here it is held to conservation and to exactly-once at quiescence;
`tests/test_fanin_mesh.py` holds it to the protocol with the layout in
it (`benchmarks/reference_fanin_mesh.py`), tick by tick.
"""

import contextlib
import json
import os

import numpy as np
import pytest

from benchmarks import reference_fanin as ref
from benchmarks.worlds import fanin
from _rebuild import block_indices
from _hlo import bare_hlo

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TICKS = 96


def _files():
    with open(os.path.join(ROOT, "benchmarks/configs/fanin-zipf.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmarks/traffic/steady.json")) as f:
        return cfg, json.load(f)


def _world(actors, seed, *, traffic=(), **options):
    cfg, mix = _files()
    cfg["actors"] = actors
    cfg["runtime_options"] = {**cfg["runtime_options"], "compile_cache": "off",
                              "tuning_cache": "off", **options}
    return fanin.build(cfg, {**mix, **dict(traffic)}, seed)


def _same(seen: dict, want: dict, tick: int) -> None:
    for key in want:
        off = np.flatnonzero(np.asarray(seen[key]) != np.asarray(want[key]))
        assert off.size == 0, (tick, key, off[:8], seen[key][off[:8]],
                               want[key][off[:8]])


def _conserved(world) -> None:
    kept = world.conservation()
    assert kept["deficit"] == 0 and all(kept["checks"].values()), kept


@pytest.mark.parametrize("delivery", ["plan", "cosort"])
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("actors", [2048, 64], ids=["256+1792", "8+56"])
def test_one_shard_follows_the_protocol_tick_by_tick(actors, seed, delivery):
    world = _world(actors, seed, delivery=delivery)
    rt, spill_peak = world.rt, 0
    muted_before = np.zeros(world.p, bool)
    mutes = np.zeros(world.p, np.int64)
    for tick in range(1, TICKS + 1):
        assert rt.run(max_steps=1) == 0
        seen = world.observed()
        _same(seen, world.reference(tick), tick)
        mutes += seen["muted"] & ~muted_before
        muted_before = seen["muted"]
        spill_peak = max(spill_peak, int(seen["spilled"].sum()))
        if tick % 16 == 0:
            _conserved(world)
    plain = world._ticks
    # (at 8 + 56 no mailbox is offered more than it has room for: that
    # size mutes on overload alone, the larger one on rejection too)
    spills = actors > 64
    assert rt.counter("n_rejected") == plain.n_rejected
    assert (plain.n_rejected > 0) == spills
    # (a producer released and muted again within one tick is one more
    # transition than the end-of-tick flags show)
    assert rt.counter("n_mutes") == plain.n_mutes >= mutes.sum()
    assert rt.counter("n_badmsg") == rt.counter("n_deadletter") == 0
    # the spill's bound: one parked item a producer, at the very most
    assert spill_peak == plain.spill_peak <= world.p
    assert (spill_peak > 0) == spills
    # long enough to mean something: some producer was muted, released
    # and muted again more than once (fill -> mute -> drain -> burst)
    assert mutes.max() >= 3
    rt.stop()


@pytest.mark.parametrize("delivery", ["plan", "cosort"])
def test_one_aggregator_under_every_producer(delivery):
    """Upstream's shape: a single aggregator. After each unmute every
    producer bursts at once into a mailbox drained to the unmute line:
    it accepts 48 or more in one tick, so the rebuild runs its deepest."""
    world = _world(64, 0, delivery=delivery, analysis=1)
    world.out[:] = 0
    world.rt.set_fields(fanin.Producer, world.prod_ids,
                        out=world.agg_ids[world.out])
    world._ticks = ref.Ticks(world.out, world.a, **world.protocol)
    cap = world.rt.opts.mailbox_cap
    bursts = producer_slots = 0
    seen = world.observed()
    for tick in range(1, TICKS + 1):
        before, sent = seen["queued"][0], seen["sent"]
        assert world.rt.run(max_steps=1) == 0
        seen = world.observed()
        _same(seen, world.reference(tick), tick)
        drained = min(before, world.rt.opts.batch)
        bursts += seen["queued"][0] - (before - drained) >= cap - 16
        # a producer that ran sent itself its next `produce`: the one
        # block Producer's rows ever need, whatever the aggregator took,
        # as wide as the producers that ran and, full width, the ONE
        # rank deep that any of them holds (tests/_rebuild.py)
        ran = int((seen["sent"] > sent).sum())
        producer_slots += block_indices(world.p, ran, 1) if ran else 0
        if tick % 16 == 0:
            _conserved(world)
    assert bursts >= 2
    # the lane sums, block by block, what the block read over the
    # COHORT's rows (ISSUE 36, 39): one aggregator row ever receives, so
    # every block of Aggregator's is a compacted one
    slots = world.rt.profile()["phases"]["rebuild"]
    blocks, rest = divmod(slots - producer_slots,
                          block_indices(world.a, 1, 8))
    assert rest == 0
    assert blocks >= 6 * bursts               # 48 accepted: 6 blocks of 8
    world.rt.stop()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_mesh_conserves_and_counts_every_item_once(seed):
    """Finite producers (`hops` items each) on `worlds/fanin.py` as it
    is: its spill is sized for one item a producer, a mesh needs two
    (`worlds/fanin_mesh.py` sizes an endless one)."""
    hops = 3
    world = _world(2048, seed, mesh_shards=4, traffic={"hops": hops})
    rt = world.rt
    for _ in range(4):
        rt.run(max_steps=16)
        kept = world.conservation()
        assert kept["deficit"] == 0
        assert kept["checks"]["conservation_every_aggregator"], kept
    assert rt.run() == 0                      # to quiescence
    seen = world.observed()
    assert (seen["sent"] == hops).all() and not seen["muted"].any()
    assert seen["queued"].sum() == 0 and seen["spilled"].sum() == 0
    assert np.array_equal(seen["total"],
                          hops * np.bincount(world.out, minlength=world.a))
    kept = world.conservation()
    assert kept["deficit"] == 0
    assert kept["checks"]["conservation_every_aggregator"]
    assert rt.counter("n_rejected") > 0 and rt.counter("n_mutes") > 0
    assert rt.counter("n_badmsg") == rt.counter("n_deadletter") == 0
    rt.stop()


def _lowered_window():
    import jax
    import jax.numpy as jnp

    from ponyc_tpu.runtime import engine
    world = _world(64, 0)
    rt = world.rt
    lowered = jax.jit(engine.build_multi_step_gated(rt.program, rt.opts)) \
        .lower(rt.state, *rt._empty_inject, jnp.int32(4), jnp.bool_(True),
               rt._zero_aux)
    rt.stop()
    return lowered


def test_pressure_scopes_are_named_and_are_metadata_only(monkeypatch):
    """The pressure branch's two halves carry their own scopes in the
    fan-in's window, and the optimised HLO is the same program with the
    scope helper stubbed out."""
    from ponyc_tpu.runtime import state
    assert {"delivery/pressure/spill", "delivery/pressure/mute"} \
        <= set(state.STEP_SCOPES)
    lowered = _lowered_window()
    text = lowered.as_text(debug_info=True)
    for scope in ("delivery/pressure/spill", "delivery/pressure/mute"):
        assert f"{state.SCOPE_PREFIX}/{scope}/" in text, scope
    scoped = lowered.compile().as_text()
    monkeypatch.setattr(state, "_named_scope",
                        lambda _name: contextlib.nullcontext())
    bare = _lowered_window().compile().as_text()
    assert "pony/" not in bare
    assert bare_hlo(scoped) == bare_hlo(bare)
