"""HPCC RandomAccess over a table held in actor heaps
(`benchmarks/worlds/gups.py`, the world of the cell `gups-hpcc.stream`)
against its plain reference (`benchmarks/reference_gups.py`), on the
CPU at small sizes: 64 updaters holding 64 words each (a table of
2^12), 64 streamers.

The updaters read and write the device blob pool from inside their
behaviours, so this is also where the pool's bulk store, its flat
layout and the `pony/dispatch/heap` scope are held to their word.
"""

import contextlib
import json
import os
import re

import jax
import numpy as np
import pytest

from benchmarks import reference_gups as ref
from benchmarks.worlds import gups
from ponyc_tpu import Runtime, RuntimeOptions
from _hlo import bare_hlo

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ACTORS, SLICE = 128, 64
SEEDS, DELIVERIES = [0, 1, 2], ["plan", "cosort"]
POOL = dict(mailbox_cap=8, batch=2, msg_words=1, spill_cap=64,
            inject_slots=8, blob_slots=128, blob_words=4,
            compile_cache="off", tuning_cache="off")


def _world(seed, *, traffic=(), **options):
    with open(os.path.join(ROOT, "benchmarks/configs/gups-hpcc.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmarks/traffic/stream.json")) as f:
        mix = json.load(f)
    cfg.update(actors=ACTORS, slice_words=SLICE)
    cfg["runtime_options"] = {**cfg["runtime_options"], "compile_cache": "off",
                              "tuning_cache": "off", **options}
    return gups.build(cfg, {**mix, **dict(traffic)}, seed)


def _on_the_invariant(world, tick=None) -> None:
    found = world.check()
    assert found["words_off"] == 0 and found["updaters_off"] == 0 \
        and all(found["checks"].values()), (tick, found)
    assert not any(world.errors().values()), (tick, world.errors())


@pytest.mark.parametrize("delivery", DELIVERIES)
@pytest.mark.parametrize("seed", SEEDS)
def test_world_keeps_the_invariant_tick_by_tick(seed, delivery):
    """64 ticks, one at a time: after each, every table word and every
    updater is on the reference's invariant, every streamer has
    dispatched exactly once more and its generator is the reference's."""
    world = _world(seed, delivery=delivery)
    rt = world.rt
    assert world.rt.counter("n_blob_alloc") == 64 and not rt._host_blobs
    for tick in range(1, 65):
        assert rt.run(max_steps=1) == 0
        _on_the_invariant(world, tick)
        counts = world.counts()
        assert (counts[64:] == tick).all()            # one dispatch a tick
        assert counts[:64].sum() + world.held() - 64 == 4 * 64 * tick
    assert world.rt.counter("n_blob_alloc") == 64
    rt.stop()


@pytest.mark.parametrize("delivery", DELIVERIES)
@pytest.mark.parametrize("seed", SEEDS)
def test_finite_run_is_the_reference_and_replays_to_the_identity(seed,
                                                                 delivery):
    """10 dispatches a streamer, run to quiescence: the table equals
    the reference's on every word, every update was applied, and
    HPCC's verification holds — the same streams applied once more
    give Table[i] = i back."""
    world = _world(seed, traffic={"hops": 10}, delivery=delivery)
    rt = world.rt
    assert rt.run(max_steps=400) == 0 and world.held() == 0
    _on_the_invariant(world)
    want = ref.Reference(world.rng0, 64, SLICE, 4, hops=10)
    want.advance_to(np.full(64, 10))
    table = world.table()
    assert np.array_equal(table, want.table)
    assert not np.array_equal(table, np.arange(64 * SLICE))
    assert np.array_equal(world.counts()[:64], want.generated)
    assert world.counts()[:64].sum() == 64 * 10 * 4
    assert np.array_equal(ref.replay(table, world.rng0, 4, 10),
                          np.arange(64 * SLICE, dtype=np.uint32))
    rt.stop()


def _same_leaves(a, b) -> None:
    la, lb = jax.tree.leaves(a.state), jax.tree.leaves(b.state)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("source", ["words", "fill", "zeros"])
def test_bulk_store_equals_single_stores(source):
    """`blob_store_many(64, ...)` leaves the state that 64 `blob_store`
    calls leave, leaf for leaf: words, handles, generations,
    `n_blob_alloc` — into a fresh pool's first half, and round the
    slots a used pool has left."""
    words = np.random.default_rng(3).integers(
        -2**31, 2**31, (64, 4), dtype=np.int64).astype(np.int32)
    if source == "fill":
        words = (np.arange(64)[:, None] * 4 + np.arange(4)).astype(np.int32)
    elif source == "zeros":
        words = np.zeros((64, 4), np.int32)
    many_args = {"words": dict(words=words), "zeros": {},
                 "fill": dict(fill=lambda k, w: k * 4 + w)}[source]
    def pool(**kw):
        return Runtime(RuntimeOptions(**{**POOL, **kw})) \
            .declare(gups.actor_types(4, 4, 1)[0], 4).start()
    single, many = pool(), pool()
    for rt in (single, many):                  # a used pool: holes at 1, 3
        held = [rt.blob_store([7, 7]) for _ in range(5)]
        rt.blob_free_host(held[1])
        rt.blob_free_host(held[3])
    one = np.array([single.blob_store(w) for w in words], np.int32)
    bulk = many.blob_store_many(64, **many_args)
    assert np.array_equal(one, bulk)
    assert set(bulk.tolist()) <= many._host_blobs == single._host_blobs
    assert many.counter("n_blob_alloc") == 69 and many.blobs_in_use == 67
    _same_leaves(single, many)
    assert np.array_equal(many.blob_fetch_many(bulk), words)
    assert np.array_equal(many.blob_fetch(int(bulk[1])), words[1])
    # a whole fresh pool: the slice update in place
    whole, ones = pool(blob_slots=64), pool(blob_slots=64)
    bulk = whole.blob_store_many(64, **many_args)
    assert np.array_equal(bulk, [ones.blob_store(w) for w in words])
    _same_leaves(ones, whole)
    assert np.array_equal(whole.blob_fetch_many(bulk[::-1]), words[::-1])
    whole.blob_free_host(int(bulk[0]))
    whole.blob_store([1])                      # slot 0 again, a new life
    with pytest.raises(KeyError, match="STALE"):
        whole.blob_fetch_many(bulk[:2])
    for rt in (single, many, whole, ones):
        rt.stop()


def test_field_held_blobs_are_the_actors_own_and_survive_gc():
    """`spawn_many(Updater, table=handles)` moves the blobs into the
    updaters' fields: the host holds no root any more, and a collection
    sweeps none of them."""
    world = _world(1)
    rt = world.rt
    assert not rt._host_blobs and rt.blobs_in_use == 64
    assert rt.run(max_steps=5) == 0
    before = world.table()
    assert rt.gc() == 0 and rt.blobs_in_use == 64
    assert np.array_equal(world.table(), before)
    assert rt.run(max_steps=5) == 0
    _on_the_invariant(world)
    rt.stop()


def _lowered(rt):
    """The window of a started runtime, lowered; the runtime stopped."""
    import jax.numpy as jnp

    from ponyc_tpu.runtime import engine
    lowered = jax.jit(engine.build_multi_step_gated(rt.program, rt.opts)) \
        .lower(rt.state, *rt._empty_inject, jnp.int32(4), jnp.bool_(True),
               rt._zero_aux)
    rt.stop()
    return lowered


def _lowered_window(delivery):
    return _lowered(_world(0, delivery=delivery).rt)


@pytest.mark.parametrize("delivery", DELIVERIES)
def test_heap_scope_is_named_and_is_metadata_only(delivery, monkeypatch):
    """The pool's checks, gathers and scatters carry `pony/dispatch/heap`
    in this world's window, inside the dispatch, and the optimised HLO
    is the same program with the scope helper stubbed out."""
    from ponyc_tpu.runtime import state
    assert "dispatch/heap" in state.STEP_SCOPES
    lowered = _lowered_window(delivery)
    text = lowered.as_text(debug_info=True)
    for op in ("get/jit(_take", "set/scatter"):   # blob_get, blob_set
        assert f"pony/dispatch/heap/{op}" in text, op
    scoped = lowered.compile().as_text()
    assert re.search(r'op_name="[^"]*/pony/dispatch/[^"]*/pony/dispatch/heap/',
                     scoped), "the heap's operations lie inside the dispatch"
    monkeypatch.setattr(state, "_named_scope",
                        lambda _name: contextlib.nullcontext())
    bare = _lowered_window(delivery).compile().as_text()
    assert "pony/" not in bare
    assert bare_hlo(scoped) == bare_hlo(bare)


def _scatters(text):
    """(attributes, operand types) of every scatter in a lowered
    module's text."""
    return re.findall(r'"stablehlo\.scatter"\([^)]*\) <\{([^}]*)\}>.*?'
                      r'\}\) : \(([^)]*)\) ->', text, flags=re.S)


@pytest.mark.parametrize("world", DELIVERIES + ["blob-free"])
def test_the_heaps_write_is_a_sorted_unique_scatter(world):
    """Every scatter on the pool in this world's window is declared
    sorted and unique and takes the unsigned keys of
    `BlobPoolView.ordered`, whose sort lies under `pony/dispatch/heap`;
    the window of a world without a blob cohort holds neither."""
    if world == "blob-free":
        from ponyc_tpu.models import ring
        rt, _ids = ring.build(8, RuntimeOptions(
            mailbox_cap=4, batch=1, max_sends=1, msg_words=1,
            inject_slots=8, compile_cache="off", tuning_cache="off"))
        text = _lowered(rt).as_text(debug_info=True)
        assert "pony/dispatch/heap" not in text
        assert _scatters(text) and not [
            t for _, t in _scatters(text) if "ui32" in t]
        return
    text = _lowered_window(world).as_text(debug_info=True)
    pool = f"tensor<{ACTORS // 2 * SLICE}xi32>"
    on_pool = [(a, t) for a, t in _scatters(text) if t.startswith(pool)]
    assert len(on_pool) >= 1
    for attrs, types in on_pool:
        assert "indices_are_sorted = true" in attrs \
            and "unique_indices = true" in attrs, attrs
        assert types == f"{pool}, tensor<64x1xui32>, tensor<64xi32>"
    assert "pony/dispatch/heap/set/sort" in text
    assert "pony/dispatch/heap/set/cond" in text  # the breach of iso

