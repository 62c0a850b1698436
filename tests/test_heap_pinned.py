"""A handle that cannot change is checked once a dispatch.

A Blob state field that every behaviour of its cohort hands back as the
very tracer it was given, in a cohort that neither allocates nor frees,
is checked against the pool's generation and used tables ONCE, before
the scan over the batch slots (`engine._cohort_dispatch`, `pinned`;
`api.BlobPoolView.resolved`). Every other handle — a message argument,
a field some behaviour overwrites, any handle of a cohort that can
allocate or free — is checked where it is used, as ever.

Held here: which cohorts are pinned and that the compiled window shows
it (no generation or used-flag gather left in the scan's body); that
pinned and unpinned cohorts alike give the pool the semantics written
out in NumPy, word for word; that a stale, null, freed or duplicated
handle sitting in a pinned field reads 0 and writes nothing in every
slot, bit for bit what the unpinned compile of the same behaviours
gives; and the analysis dump's `pinned_handles`.
"""

import json
import os
import re

import numpy as np
import pytest

from benchmarks.worlds import gups
from ponyc_tpu import (I32, Blob, Runtime, RuntimeOptions, actor,
                       behaviour)
from ponyc_tpu.models import records
from ponyc_tpu.ops import pack
from ponyc_tpu.runtime import engine
from _hlo import window_texts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
W, ACTORS, BATCH = 4, 24, 4
OPTS = dict(mailbox_cap=8, batch=BATCH, max_sends=1, msg_words=2,
            inject_slots=256, spill_cap=64, blob_slots=512, blob_words=W,
            compile_cache="off", tuning_cache="off")


# ------------------------------------------------------ the cohort shapes

def _bump(self, st, i: I32, v: I32):
    """Read-modify-write through the FIELD's handle."""
    h = st["table"]
    old = self.blob_get(h, i)
    self.blob_set(h, i, old ^ v)
    return {**st, "seen": st["seen"] + old + self.blob_length(h)}


def _peek(self, st, h: I32, i: I32):
    """Read through a handle that came BY MESSAGE (an int: a copy)."""
    return {**st, "seen": st["seen"] + self.blob_get(h, i)}


def _adopt(self, st, h: Blob):
    return {**st, "table": h}


def _discard(self, st, h: I32, _: I32):
    self.blob_free(h)
    return st


def _fresh(self, st, v: I32, _: I32):
    h = self.blob_alloc()
    self.blob_set(h, 0, v)
    return {**st, "spare": h}


# shape -> (the behaviours beside bump and peek, MAX_BLOBS, pinned fields)
SHAPES = {
    "passthrough": ({}, 0, ["table", "spare"]),
    "stores-a-message-handle": ({"adopt": _adopt}, 0, ["spare"]),
    "sibling-frees": ({"discard": _discard}, 0, []),
    "sibling-allocs": ({"fresh": _fresh}, 1, []),
}


def _holder(shape):
    extra, max_blobs, _ = SHAPES[shape]
    body = {"__annotations__": {"table": Blob, "spare": Blob, "seen": I32},
            "MAX_SENDS": 0, "MAX_BLOBS": max_blobs,
            "bump": behaviour(_bump), "peek": behaviour(_peek),
            **{name: behaviour(fn) for name, fn in extra.items()}}
    return actor(type("Holder", (), body))


def _world(shape, seed):
    """ACTORS holders, each owning a blob of W words made by the host."""
    Holder = _holder(shape)
    rt = Runtime(RuntimeOptions(**OPTS))
    rt.declare(Holder, ACTORS).start()
    rng = np.random.default_rng(seed)
    words = rng.integers(1, 1000, (ACTORS, W)).astype(np.int32)
    handles = rt.blob_store_many(ACTORS, words)
    ids = rt.spawn_many(Holder, ACTORS, table=handles, spare=-1, seen=0)
    return rt, Holder, ids, [int(h) for h in handles], words, rng


class Model:
    """The pool's semantics, one actor and one message at a time: a
    handle is live iff it is the handle its blob was made or adopted
    under and the blob was not freed; a read through anything else is
    0, a write is dropped, a length is 0."""

    def __init__(self, handles, words):
        self.blobs = {h: list(map(int, w)) for h, w in zip(handles, words)}
        self.freed = {}                     # words stay where they were
        self.table = list(handles)
        self.spare = [None] * len(handles)  # words of an allocated spare
        self.seen = np.zeros(len(handles), np.int32)

    def get(self, h, i):
        return self.blobs[h][i] if h in self.blobs and 0 <= i < W else 0

    def deliver(self, a, kind, x, y):
        if kind == "bump":
            h, old = self.table[a], self.get(self.table[a], x)
            if h in self.blobs and 0 <= x < W:
                self.blobs[h][x] = old ^ y
            self.seen[a] += np.int32(old + (W if h in self.blobs else 0))
        elif kind == "peek":
            self.seen[a] += np.int32(self.get(x, y))
        elif kind == "adopt":
            self.table[a] = x
        elif kind == "discard":
            if x in self.blobs:
                self.freed[x] = self.blobs.pop(x)
        elif kind == "fresh":
            self.spare[a] = [x] + [0] * (W - 1)


def _stale(h):
    return int(pack.blob_handle(pack.blob_slot(h), pack.blob_gen_of(h) + 1))


def _pool(rt):
    return {name: np.asarray(getattr(rt.state, "blob_" + name))
            for name in ("data", "used", "gen", "len")}


def _at(h_or_slot):
    """Where the W words of a handle's (or a slot's) blob lie in the
    flat pool (`state.pool_index`: word-major)."""
    return np.arange(W) * OPTS["blob_slots"] + int(
        pack.blob_slot(int(h_or_slot)))


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_every_cohort_shape_gives_the_numpy_statements_pool(shape, seed):
    """Rounds of up to 6 messages an actor (batch 4: some wait a tick),
    bumps through the field and peeks through copies of live, stale and
    null handles, and the shape's own behaviour in between — among them
    a free of the field's own blob with reads behind it in the same
    batch, which read 0 as they always did. The pool, word for word,
    the tables, and every actor's sum are the model's."""
    rt, Holder, ids, handles, words, rng = _world(shape, seed)
    assert engine.pinned_handles(rt.program, rt.opts) == {
        "Holder": SHAPES[shape][2]}
    model = Model(handles, words)
    before = _pool(rt)["data"].copy()
    own = list(handles)                # a copy the host kept (forged)
    for _ in range(5):
        for a in rng.permutation(ACTORS):
            kinds = ["bump", "bump", "peek"] + list(SHAPES[shape][0])
            for kind in rng.choice(kinds, rng.integers(0, 7)):
                if kind == "bump":
                    x, y = int(rng.integers(-1, W + 1)), int(
                        rng.integers(1, 1000))
                elif kind == "peek":
                    x = [own[a], _stale(own[a]), -1, handles[a]][
                        rng.integers(0, 4)]
                    y = int(rng.integers(-1, W + 1))
                elif kind == "adopt":
                    x = int(rt.blob_store(
                        rng.integers(1, 1000, W).astype(np.int32),
                        near=int(ids[a])))
                    y, own[a] = None, x
                    model.blobs[x] = [int(w) for w in rt.blob_fetch(x)]
                elif kind == "discard":
                    x, y = [own[a], _stale(own[a]), -1][
                        rng.integers(0, 3)], 0
                else:
                    x, y = int(rng.integers(1, 1000)), 0
                args = (x,) if y is None else (x, y)
                rt.send(int(ids[a]), getattr(Holder, kind), *args)
                model.deliver(a, kind, x, y)
        assert rt.run() == 0
    state, pool = rt.cohort_state(Holder), _pool(rt)
    np.testing.assert_array_equal(state["seen"], model.seen)
    np.testing.assert_array_equal(state["table"], model.table)
    expect = before.copy()
    if shape == "sibling-frees":
        assert model.freed              # the case was drawn
    for h, ws in {**model.blobs, **model.freed}.items():
        slot = int(pack.blob_slot(h))
        expect[_at(h)] = ws
        assert bool(pool["used"][slot]) == (h in model.blobs), h
        assert int(pool["gen"][slot]) == int(pack.blob_gen_of(h))
    for a, ws in enumerate(model.spare):
        if ws is None:
            assert state["spare"][a] == -1
        else:
            h = int(state["spare"][a])
            assert pool["used"][pack.blob_slot(h)]
            expect[_at(h)] = ws
    # a spare that was replaced is a leaked, zeroed blob holding word 0
    leaked = np.setdiff1d(
        np.flatnonzero(pool["used"]),
        [pack.blob_slot(h) for h in model.blobs] + [
            pack.blob_slot(int(h)) for h in state["spare"] if h >= 0])
    for slot in leaked:
        expect[_at(slot)] = pool["data"][_at(slot)]
        assert (pool["data"][_at(slot)[1:]] == 0).all()
    np.testing.assert_array_equal(pool["data"], expect)
    for name in ("blob_fail", "blob_budget_fail", "n_blob_remote",
                 "n_badmsg", "n_deadletter", "n_rejected"):
        assert rt.counter(name) == 0, name
    rt.stop()


# ------------------------------- what a pinned field may hold, by the host

def _hostile(shape, seed=3):
    """The passthrough behaviours with fields the host overwrote: actor
    0 a stale generation, 1 a null handle, 2 a handle whose blob the
    host freed, 3 a slot out of range, 4 and 5 the SAME live blob (a
    forged duplicate; both bump word 1 in one tick); 6... honest.
    Returns everything the run left."""
    rt, Holder, ids, handles, words, _ = _world(shape, seed)
    poked = list(handles)
    poked[0], poked[1] = _stale(handles[0]), -1
    poked[3] = int(pack.blob_handle(rt.opts.blob_slots + 5, 1))
    poked[5] = handles[4]
    rt.set_fields(Holder, ids, table=np.asarray(poked, np.int32))
    rt.blob_free_host(handles[2])
    for rnd in range(2):
        for a in range(8):
            for k in range(BATCH + 2):          # every slot, and a tick more
                rt.send(int(ids[a]), Holder.bump, (k + a) % W if a != 5
                        else 1, 100 * rnd + 10 * a + k + 1)
            rt.send(int(ids[a]), Holder.peek, handles[a], 0)
        rt.send(int(ids[4]), Holder.bump, 1, 77)
        assert rt.run() == 0
    out = {**_pool(rt), **rt.cohort_state(Holder),
           "pinned": engine.pinned_handles(rt.program, rt.opts)["Holder"]}
    rt.stop()
    return out, handles, words


def test_a_pinned_field_holding_a_dead_handle_reads_0_and_writes_nothing():
    """Stale, null, freed, out of range: every slot's get reads 0 (the
    actor's sum holds only what its peek through the honest copy read),
    every set is dropped (the blob's words are the host's), in all six
    slots a round. The duplicate's two owners both write: one word
    keeps one of the values, as `ordered` says. And all of it, bit for
    bit, is what the same behaviours give when the cohort is NOT pinned
    (a sibling that frees, never sent)."""
    pinned, handles, words = _hostile("passthrough")
    unpinned, _, _ = _hostile("sibling-frees")
    assert pinned.pop("pinned") == ["table", "spare"]
    assert unpinned.pop("pinned") == []
    for key in pinned:
        np.testing.assert_array_equal(pinned[key], unpinned[key], key)
    for a in (0, 1, 2, 3):
        np.testing.assert_array_equal(pinned["data"][_at(handles[a])],
                                      words[a])
    # the live copies read word 0 twice; the freed blob's reads 0 too
    np.testing.assert_array_equal(
        pinned["seen"][:4], [2 * words[0][0], 2 * words[1][0], 0,
                             2 * words[3][0]])
    assert pinned["seen"][6] != 0 and not pinned["used"][
        pack.blob_slot(handles[2])]
    assert (pinned["data"][_at(handles[6])] != words[6]).any()


# --------------------------------------------- the window of the GUPS cell

def _gups(actors, slice_words, seed=5, hops=1 << 30):
    with open(os.path.join(ROOT, "benchmarks/configs/gups-hpcc.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmarks/traffic/stream.json")) as f:
        mix = json.load(f)
    cfg.update(actors=actors, slice_words=slice_words)
    cfg["runtime_options"] = {**cfg["runtime_options"],
                              "compile_cache": "off", "tuning_cache": "off"}
    return gups.build(cfg, {**mix, "hops": hops}, seed)


def _heap_gathers(hlo, cohort):
    """The heap's gathers of `cohort`'s dispatch in an optimised HLO
    text, as (those outside the batch scan, those in its body), each a
    result type: `pred` the used flags', `s32` the generations' or the
    pool words'."""
    outside, inside = [], []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\w+)\[[\d,]*\]\S* "
                     r"gather\(.*op_name=\"([^\"]*)\"", line)
        if not m or f"cohort/{cohort}/" not in m.group(2) \
                or "pony/dispatch/heap" not in m.group(2):
            continue
        in_scan = "/while/body/" in m.group(2).split(f"cohort/{cohort}/")[1]
        (inside if in_scan else outside).append(m.group(1))
    return sorted(outside), sorted(inside)


def test_the_gups_window_checks_the_handle_before_the_scan():
    """2,048 actors: the Updaters' scan body holds one gather, the
    word's; the generation's and the used flag's are in its prologue,
    one each. Then 12 ticks, and the table and `applied` are the
    reference's."""
    world = _gups(2048, 64)
    rt = world.rt
    assert engine.pinned_handles(rt.program, rt.opts) == {
        "Updater": ["table"], "Streamer": []}
    _, hlo = window_texts(rt)
    assert _heap_gathers(hlo, "Updater") == (["pred", "s32"], ["s32"])
    for _ in range(12):
        assert rt.run(max_steps=1) == 0
    found = world.check()
    assert found["words_off"] == 0 and found["updaters_off"] == 0 \
        and all(found["checks"].values()), found
    assert not any(world.errors().values()), world.errors()
    assert rt.counter("n_blob_alloc") == 1024
    assert world.counts()[:1024].sum() > 12 * 1024     # updates applied
    rt.stop()


def test_an_unpinned_cohort_compiles_the_checks_in_the_scan():
    """The control for the count above: a cohort whose sibling frees
    keeps the generation's and the used flag's gathers in the body."""
    rt = _world("sibling-frees", 0)[0]
    _, hlo = window_texts(rt)
    outside, inside = _heap_gathers(hlo, "Holder")
    assert outside == [] and inside.count("pred") >= 1 \
        and inside.count("s32") >= 2, (outside, inside)
    rt.stop()


def test_a_trace_that_disagrees_with_the_probe_fails_the_build():
    """The probe reads what the behaviours do; the window is built from
    the same functions. One that overwrites its handle the second time
    it is traced would be given a stale answer: the build raises.
    (Told apart here by the checked handles, `resolved`, that only the
    real trace's view is handed.)"""
    def fickle(self, st, i: I32, v: I32):
        self.blob_set(st["table"], i, v)
        if self._blob.resolved is None:
            return st
        return {**st, "table": st["table"] + 1}

    Holder = actor(type("Holder", (), {
        "__annotations__": {"table": Blob}, "MAX_SENDS": 0,
        "bump": behaviour(fickle)}))
    rt = Runtime(RuntimeOptions(**OPTS))
    rt.declare(Holder, 4).start()
    with pytest.raises(RuntimeError, match="probe and the trace disagree"):
        rt.run(max_steps=1)
    rt.stop()


# ------------------------------------------------------ the analysis dump

def test_the_dump_names_the_pinned_handles():
    """`profile()`'s cohort rows and the dump's text: `["table"]` for
    the GUPS Updater, `[]` for every cohort of `records` (they allocate,
    free, or take their handles by message)."""
    import io
    world = _gups(128, 64, hops=2)
    world.rt.stop()
    cfg_opts = dict(world.rt.opts.__dict__, analysis=1)
    rt = Runtime(RuntimeOptions(**cfg_opts))
    rt.declare(world.Updater, 64).declare(world.Streamer, 64).start()
    rows = rt.profile()["cohorts"]
    assert rows["Updater"]["pinned_handles"] == ["table"]
    assert rows["Streamer"]["pinned_handles"] == []
    from ponyc_tpu import analysis
    text = analysis.attach(rt).dump(out=io.StringIO())
    assert re.search(r"cohort Updater: .* pinned_handles=table", text)
    assert re.search(r"cohort Streamer: .* pinned_handles=-", text)
    rt.stop()
    rec = records.build(4, 2, RuntimeOptions(
        mailbox_cap=8, batch=2, max_sends=2, msg_words=2, inject_slots=8,
        blob_slots=64, blob_words=records.W))[0]
    assert engine.pinned_handles(rec.program, rec.opts) == {
        "RecSource": [], "RecWorker": [], "RecSink": []}
    rec.stop()
