"""message-ubench on a mesh (`benchmarks/worlds/ubench_mesh.py`, the
world of the cell `ubench-4m-mesh4.remote`) against the plain
references (`benchmarks/reference.py`, `benchmarks/reference_mesh.py`),
on the suite's virtual CPU devices at a small size.

What the mesh may not change: every actor's count after every tick is
the layout-free reference's, on 1, 2 and 4 shards alike. What it adds
is counted: the route's `n_routed` / `n_routed_remote` are the
reference's sends and crossings tick by tick, the route spill stays
empty at the program's own bucket, and a bucket made too small on
purpose parks and retries without losing or doubling a message. The
names and leaves the mesh adds exist on a mesh only.
"""

import contextlib
import functools
import json
import os

import jax
import numpy as np
import pytest

from benchmarks import reference_mesh
from benchmarks.worlds import ubench_mesh
from ponyc_tpu.runtime import engine
from ponyc_tpu.runtime.state import (LIST_COUNTERS, ROUTE_COUNTERS,
                                     SCOPE_PREFIX)
from _hlo import bare_hlo
from test_run_loop import recording  # noqa: F401  (a fixture)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ACTORS, TICKS = 1024, 12
ROUTE_SCOPES = ("route/sort", "route/bucket", "route/exchange",
                "route/spill", "route/spill/lookup", "route/spill/mute",
                "route/unpack")


def _world(shards, recipients, seed=7, actors=ACTORS, **options):
    with open(os.path.join(
            ROOT, "benchmarks/configs/ubench-4m-mesh4.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmarks/traffic/remote.json")) as f:
        mix = json.load(f)
    cfg["actors"] = actors
    cfg["runtime_options"] = {
        **cfg["runtime_options"], "mesh_shards": shards,
        "compile_cache": "off", "tuning_cache": "off", **options}
    return ubench_mesh.build(cfg, {**mix, "recipients": recipients}, seed)


def _spilled(rt) -> int:
    return int(np.asarray(rt.state.rspill_count).sum())


@functools.lru_cache(maxsize=None)
def _ticked(shards, recipients):
    """The world run tick by tick: per tick every actor's count (by id
    offset), the route counters and the route spill; and the world's
    references for the same ticks."""
    world = _world(shards, recipients)
    rt, seen = world.rt, []
    for _tick in range(TICKS):
        assert rt.run(max_steps=1) == 0
        seen.append((world.counts(),
                     tuple(rt.counter(c) for c in ROUTE_COUNTERS),
                     _spilled(rt)))
    want = [world.reference(t) for t in range(1, TICKS + 1)]
    crossings = world.route_reference(TICKS)
    errors = {c: rt.counter(c) for c in ("n_rejected", "n_badmsg",
                                         "n_deadletter", "n_mutes")}
    rt.stop()
    return seen, want, crossings, errors


@pytest.mark.parametrize("recipients", ["cycle", "random"])
@pytest.mark.parametrize("shards", [1, 2, 4])
def test_every_actor_follows_the_reference_on_any_layout(shards, recipients):
    seen, want, (sent, remote), errors = _ticked(shards, recipients)
    for tick, ((counts, routed, spilled), ref) in enumerate(zip(seen, want)):
        off = np.flatnonzero(counts != ref)
        assert off.size == 0, (tick, off[:8], counts[off[:8]], ref[off[:8]])
        # the route's counters are the reference's sends and crossings
        # (nothing is routed, and nothing counted, on one shard)
        # and at the program's own bucket every shard of every tick
        # delivers over what arrived, the short list (`n_unpacked`);
        # nobody declares pressure and nobody is overloaded, so no
        # shard of any tick looks its entries' targets up
        # (`n_route_pressure`), at either length (`n_route_prefix`),
        # and nobody mutes (`n_remote_mutes`)
        expect = (int(sent[:tick + 1].sum()), int(remote[:tick + 1].sum()),
                  shards * (tick + 1), 0, 0, 0) if shards > 1 else (0,) * 6
        assert routed == expect, (tick, routed, expect)
        assert spilled == 0, tick
    assert not any(errors.values()), errors
    if shards > 1:
        assert 0 < remote.sum() < sent.sum()


@pytest.mark.parametrize("recipients", ["cycle", "random"])
def test_four_shards_give_one_shards_counts(recipients):
    """Layout independence, program against program: the same seeded
    world on four shards and on one, every actor, every tick."""
    one, four = _ticked(1, recipients)[0], _ticked(4, recipients)[0]
    for tick, (a, b) in enumerate(zip(one, four)):
        assert np.array_equal(a[0], b[0]), tick


@pytest.mark.parametrize("shards", [2, 4])
def test_the_deal_is_round_robin(shards):
    deal = reference_mesh.deal(ACTORS, shards)
    assert sorted(deal) == list(range(ACTORS))
    assert list(deal[:shards + 1]) == [
        s * (ACTORS // shards) for s in range(shards)] + [1]


def test_a_small_bucket_parks_and_retries_without_loss():
    """A route bucket far below the offered load: entries park in the
    route spill and their senders mute, so the ticks are no longer the
    reference's; but every message is still dispatched exactly once.
    Order-free: every dispatch sends one ping, and every ping sent has
    either shipped (counted once, in the tick it shipped) or sits in
    the route spill; the world holds its seeded pings; every behaviour
    the device counted, some actor counted."""
    world = _world(4, "random", route_bucket=256, spill_cap=8192)
    rt = world.rt
    spilled_ever = 0
    for _tick in range(TICKS):
        assert rt.run(max_steps=1) == 0
        spilled = _spilled(rt)
        spilled_ever += spilled
        dispatched = rt.counter("n_processed")
        assert rt.counter("n_routed") + spilled == dispatched
        queued = int((np.asarray(rt.state.tail, np.int64)
                      - np.asarray(rt.state.head, np.int64)).sum())
        parked = int(np.asarray(rt.state.dspill_count).sum())
        assert queued + parked + spilled == world.live
        assert int(world.counts().sum()) == dispatched
    assert spilled_ever > 0 and rt.counter("n_mutes") > 0
    assert rt.counter("n_deadletter") == rt.counter("n_badmsg") == 0
    rt.stop()


@pytest.mark.parametrize("recipients", ["cycle", "random"])
@pytest.mark.parametrize("shards", [2, 4])
def test_a_quiet_mesh_never_looks_up_pressure(shards, recipients):
    """Nobody declares pressure in this world: world bit 0 is never set
    and `route._route_spill`'s lookup of the sorted entries' targets in
    the mesh-wide pressured bits runs on no shard of any tick."""
    seen = _ticked(shards, recipients)[0]
    names = dict(zip(ROUTE_COUNTERS, seen[-1][1]))
    assert names["n_unpacked"] == shards * TICKS
    assert names["n_route_pressure"] == names["n_route_prefix"] == 0


def _window_text(rt, compiled=False):
    gated = engine.jit_multi_step_gated(rt.program, rt.opts, rt.mesh)
    lowered = gated.lower(rt.state, *rt._empty_inject, jax.numpy.int32(4),
                          jax.numpy.bool_(True), rt._zero_aux)
    if compiled:
        return lowered.compile().as_text()
    return lowered.as_text(debug_info=True)


def test_route_scopes_and_counters_exist_on_a_mesh_only():
    """A mesh's window names the route's five parts and its state holds
    the four counters; a one-shard window has no operation under
    `pony/route/*` and no such leaf (`n_prefix` is delivery's: on both)."""
    world = _world(4, "random", actors=256)
    text = _window_text(world.rt)
    for scope in ROUTE_SCOPES:
        assert f"{SCOPE_PREFIX}/{scope}/" in text, scope
    assert sorted(world.rt.state.route_counts) == sorted(LIST_COUNTERS)
    world.rt.stop()

    world = _world(1, "random", actors=256)
    text = _window_text(world.rt)
    assert f"{SCOPE_PREFIX}/route/" in text
    for scope in ROUTE_SCOPES:
        assert f"{SCOPE_PREFIX}/{scope}" not in text, scope
    assert list(world.rt.state.route_counts) == ["n_prefix"]
    assert world.rt.counter("n_routed") == 0
    assert world.rt.counter("n_routed_remote") == 0
    assert world.rt.counter("n_unpacked") == 0
    assert world.rt.counter("n_route_pressure") == 0
    assert world.rt.counter("n_route_prefix") == 0
    world.rt.stop()


def test_route_scopes_are_metadata_only(monkeypatch):
    """The mesh window compiled with the scopes and with the scope
    helper stubbed out is one program, once metadata is stripped."""
    from ponyc_tpu.runtime import state
    world = _world(4, "random", actors=256)
    scoped = _window_text(world.rt, compiled=True)
    assert "pony/route/exchange" in scoped
    world.rt.stop()
    monkeypatch.setattr(state, "_named_scope",
                        lambda _name: contextlib.nullcontext())
    world = _world(4, "random", actors=256)
    bare = _window_text(world.rt, compiled=True)
    world.rt.stop()
    assert "pony/" not in bare
    assert bare_hlo(scoped) == bare_hlo(bare)


def _route_gathers(text, scope):
    """The gather instructions of a compiled window whose op_name lies
    under `pony/<scope>` (the bounds' binary search over `shards + 1`
    queries aside: `jnp.searchsorted` reads five words its own way)."""
    return [line for line in text.splitlines()
            if " gather(" in line and f"{SCOPE_PREFIX}/{scope}/" in line
            and "jit(searchsorted)" not in line]


@pytest.mark.parametrize("msg_words", [1, 8])
def test_the_route_packs_without_reading_by_index(msg_words):
    """The regression PR 41 took out: `_route` read `dest`, `tgt`,
    `sender` and the words back through the sort's permutation and
    padded every destination's block by a dense `[shards, bucket]`
    gather. Now no gather runs under `pony/route/sort` (the sort carries
    what was read back, the words of a wide message too) and none under
    `pony/route/bucket` (a block is a slice)."""
    world = _world(4, "random", actors=256, msg_words=msg_words)
    text = _window_text(world.rt, compiled=True)
    world.rt.stop()
    assert f"{SCOPE_PREFIX}/route/bucket/dynamic_slice" in text
    assert _route_gathers(text, "route/bucket") == []
    assert _route_gathers(text, "route/sort") == []


def test_a_mesh_says_its_shards_on_start_and_on_every_launch(recording):
    """`shards=` on `pony:start` and on a window's `pony:dispatching`."""
    world = _world(4, "cycle", actors=256)
    assert world.rt.run(max_steps=3) == 0
    world.rt.stop()
    metas = {name: meta for kind, name, _depth, meta in recording
             if kind == "enter" and name in ("pony:start",
                                             "pony:dispatching")}
    assert metas["pony:start"] == {"shards": 4}
    assert metas["pony:dispatching"]["shards"] == 4
