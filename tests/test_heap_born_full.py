"""A payload is born full: `blob_alloc` opens a column, the `blob_set`s
to the handle it returned fill it, one scatter writes it.

`Context.blob_alloc` writes a fresh slot's books at once and its words
not at all; the sets that follow to the very object it returned, at
static word indices, land in the open column (`api.BlobPoolView`), and
`flush` writes the column — zeros where nothing was set — before
anything can see the pool's words. Held here: every way a behaviour can
mix allocs, sets, reads and frees against the pool's semantics written
out in NumPy one lane at a time, over a pool that starts full of other
words (a zero left unwritten shows); how many pool scatters each mix
lowers to; a forged handle that names a fresh slot; the same through the
runtime, on one shard and after a move to another; the stencil's and
GUPS's compiled windows; and the `born_full` line of the dump.
"""

import io
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ponyc_tpu import (Blob, I32, Ref, Runtime, RuntimeOptions, actor,
                       behaviour)
from ponyc_tpu.api import BlobPoolView, Context
from ponyc_tpu.ops import pack
from ponyc_tpu.runtime import engine

import _hlo

L, SLOTS, WORDS, BASE, SITES = 8, 32, 4, 64, 3
SIZE = SLOTS * WORDS
LANE = np.arange(L, dtype=np.int32)
# Three reservation windows a lane, disjoint, in no order of the lanes;
# lanes 2 and 5 found the free list empty at the last site.
RESV = BASE + np.array([[9, 3, 30, 0, 7, 22, 5, 18],
                        [4, 15, 1, 27, 2, 8, 13, 6],
                        [31, 10, -1, 12, 29, -1, 11, 20]], np.int32)
RESV[2, [2, 5]] = -1
GEN0 = (np.arange(SLOTS, dtype=np.int32) * 5) % 7
TAKE = np.array([1, 1, 1, 0, 1, 1, 1, 1], bool)


class NumpyHeap:
    """The pool's semantics, one lane at a time, in program order: what
    `Context.blob_*` mean. An alloc zeroes its slot's words at once."""

    def __init__(self, take, resv):
        self.data = np.arange(1000, 1000 + SIZE, dtype=np.int32)
        self.used = np.zeros(SLOTS, bool)
        self.len_ = np.zeros(SLOTS, np.int32)
        self.gen = GEN0.copy()
        self.take, self.resv, self.site = take, resv, 0
        self.fail, self.n_alloc, self.n_free = False, 0, 0

    def _lanes(self, *xs):
        return [np.broadcast_to(np.asarray(x), (L,)) for x in xs]

    def _slot(self, h):
        slot = int(pack.blob_slot(int(h))) - BASE
        ok = (h >= 0 and 0 <= slot < SLOTS
              and self.gen[slot] == int(pack.blob_gen_of(int(h))))
        return slot, ok

    def blob_alloc(self, length=None, when=True):
        row, self.site = self.resv[self.site], self.site + 1
        (when,) = self._lanes(when)
        ln = WORDS if length is None else length
        (ln,) = self._lanes(ln)
        h = np.full(L, -1, np.int32)
        for lane in range(L):
            if not (when[lane] and self.take[lane]):
                continue
            if row[lane] < 0:
                self.fail = True
                continue
            slot = row[lane] - BASE
            self.gen[slot] = (self.gen[slot] + 1) & pack.BLOB_GEN_MASK
            self.used[slot] = True
            self.len_[slot] = min(max(int(ln[lane]), 0), WORDS)
            self.data[slot::SLOTS] = 0
            self.n_alloc += 1
            h[lane] = pack.blob_handle(row[lane], self.gen[slot])
        return h

    def blob_set(self, h, i, v, when=True):
        h, i, v, when = self._lanes(h, i, v, when)
        for lane in range(L):
            slot, ok = self._slot(h[lane])
            if (when[lane] and self.take[lane] and ok and self.used[slot]
                    and 0 <= i[lane] < WORDS):
                self.data[i[lane] * SLOTS + slot] = v[lane]

    def blob_get(self, h, i):
        h, i = self._lanes(h, i)
        out = np.zeros(L, np.int32)
        for lane in range(L):
            slot, ok = self._slot(h[lane])
            if ok and self.used[slot] and 0 <= i[lane] < WORDS:
                out[lane] = self.data[i[lane] * SLOTS + slot]
        return out

    def blob_length(self, h):
        (h,) = self._lanes(h)
        out = np.zeros(L, np.int32)
        for lane in range(L):
            slot, ok = self._slot(h[lane])
            if ok:
                out[lane] = self.len_[slot]
        return out

    def blob_free(self, h, when=True):
        h, when = self._lanes(h, when)
        for lane in range(L):
            slot, ok = self._slot(h[lane])
            if when[lane] and self.take[lane] and ok and self.used[slot]:
                self.used[slot], self.len_[slot] = False, 0
                self.n_free += 1


# --- the programs: (ctx, inputs, xp) -> {name: lanes read} ---------------

def _full_fill(ctx, x, xp):
    h = ctx.blob_alloc()
    for w in range(WORDS):
        ctx.blob_set(h, w, x["v"] + w)
    return {"len": ctx.blob_length(h)}


def _partial_fill(ctx, x, xp):
    h = ctx.blob_alloc(length=3)
    ctx.blob_set(h, 0, x["v"])
    ctx.blob_set(h, 2, x["v"] * 3)
    return {}


def _a_mask_a_word(ctx, x, xp):
    # models/records.py's form: `when=go & (i < ln)`, ln a lane's own
    h = ctx.blob_alloc(length=x["ln"], when=x["go"])
    for w in range(WORDS):
        ctx.blob_set(h, w, x["v"] - w, when=x["go"] & (w < x["ln"]))
    return {"len": ctx.blob_length(h)}


def _same_word_twice(ctx, x, xp):
    h = ctx.blob_alloc()
    ctx.blob_set(h, 1, x["v"])
    ctx.blob_set(h, np.int32(1), x["v"] + 100, when=x["go"])
    ctx.blob_set(h, 3, 7)
    return {}


def _when_false_in_some_lanes(ctx, x, xp):
    h = ctx.blob_alloc(when=x["go"])
    for w in range(WORDS):
        ctx.blob_set(h, w, x["v"] + w)
    return {}


def _when_false_in_all_lanes(ctx, x, xp):
    h = ctx.blob_alloc(when=x["go"] & False)
    for w in range(WORDS):
        ctx.blob_set(h, w, x["v"] + w)
    return {"got": ctx.blob_get(h, 0)}


def _a_get_between_two_sets(ctx, x, xp):
    h = ctx.blob_alloc()
    ctx.blob_set(h, 1, x["v"])
    r = ctx.blob_get(h, 1)
    z = ctx.blob_get(h, 2)
    ctx.blob_set(h, 2, r + 1)
    return {"r": r, "z": z, "after": ctx.blob_get(h, 2)}


def _a_traced_index_between_static_ones(ctx, x, xp):
    h = ctx.blob_alloc()
    ctx.blob_set(h, 0, x["v"])
    ctx.blob_set(h, x["w"], x["v"] + 50)          # may name word 0 again
    ctx.blob_set(h, 0, x["v"] + 9, when=x["go"])
    return {}


def _three_allocs_open_at_once(ctx, x, xp):
    hs = [ctx.blob_alloc(length=1 + s, when=x["go"] | (s == 1))
          for s in range(SITES)]
    for w in range(WORDS):
        for s, h in enumerate(hs):
            ctx.blob_set(h, w, x["v"] * (s + 1) + w, when=(w != s))
    return {f"len{s}": ctx.blob_length(h) for s, h in enumerate(hs)}


def _alloc_then_free(ctx, x, xp):
    keep = ctx.blob_alloc()
    h = ctx.blob_alloc()
    ctx.blob_set(keep, 1, x["v"])
    ctx.blob_set(h, 0, x["v"])
    ctx.blob_free(h, when=x["go"])
    ctx.blob_set(keep, 2, x["v"] + 1)
    return {}


def _pool_exhausted_in_some_lanes(ctx, x, xp):
    ctx.blob_alloc()
    ctx.blob_alloc()
    h = ctx.blob_alloc()                           # lanes 2 and 5: none
    for w in range(WORDS):
        ctx.blob_set(h, w, x["v"] + w)
    return {"got": ctx.blob_get(h, 1), "len": ctx.blob_length(h)}


def _a_forged_handle_sets_between(ctx, x, xp):
    # `forged` is an untyped int that names the slot the alloc will
    # hand out, at the generation it will have: another object, so the
    # op it is given to flushes first and lands behind the column
    h = ctx.blob_alloc()
    ctx.blob_set(h, 0, x["v"])
    ctx.blob_set(h, 1, x["v"] + 1)
    ctx.blob_set(x["forged"], 0, x["v"] + 200, when=x["go"])
    ctx.blob_set(h, 1, x["v"] + 300)
    return {}


def _a_forged_handle_reads_between(ctx, x, xp):
    h = ctx.blob_alloc()
    ctx.blob_set(h, 3, x["v"])
    seen = ctx.blob_get(x["forged"], 3)
    unset = ctx.blob_get(x["forged"], 2)
    ctx.blob_set(h, 3, x["v"] + 1)
    return {"seen": seen, "unset": unset}


def _a_copy_of_the_handle_goes_the_eager_way(ctx, x, xp):
    h = ctx.blob_alloc()
    copy = xp.where(x["go"] | True, h, -1)         # the value, not the object
    for w in range(WORDS - 1):
        ctx.blob_set(copy, w, x["v"] + w)
    return {}


_FORGED = np.array([pack.blob_handle(s, (GEN0[s - BASE] + 1)
                                     & pack.BLOB_GEN_MASK)
                    for s in RESV[0]], np.int32)
INPUTS = {
    "v": LANE * 11 + 7,
    "go": np.array([1, 0, 1, 1, 0, 1, 1, 0], bool),
    "ln": np.array([4, 2, 0, 3, 1, 4, 9, -1], np.int32),
    "w": np.array([0, 1, 2, 3, 0, 4, -1, 2], np.int32),
    "forged": _FORGED,
}
# program: (column scatters, word scatters, allocs, sets folded, alone)
PROGRAMS = {
    _full_fill: (1, 0, 1, WORDS, 0),
    _partial_fill: (1, 0, 1, 2, 0),
    _a_mask_a_word: (1, 0, 1, WORDS, 0),
    _same_word_twice: (1, 0, 1, 3, 0),
    _when_false_in_some_lanes: (1, 0, 1, WORDS, 0),
    _when_false_in_all_lanes: (1, 0, 1, WORDS, 0),
    _a_get_between_two_sets: (1, 1, 1, 1, 1),
    _a_traced_index_between_static_ones: (1, 2, 1, 1, 2),
    _three_allocs_open_at_once: (3, 0, 3, 3 * WORDS, 0),
    _alloc_then_free: (2, 1, 2, 2, 1),
    _pool_exhausted_in_some_lanes: (3, 0, 3, WORDS, 0),
    _a_forged_handle_sets_between: (1, 2, 1, 2, 2),
    _a_forged_handle_reads_between: (1, 1, 1, 1, 1),
    _a_copy_of_the_handle_goes_the_eager_way: (1, WORDS - 1, 1, 0,
                                               WORDS - 1),
}
TAKES = {"some-lanes-taken": TAKE, "every-lane-taken": np.ones(L, bool)}


def _pool_scatters(jaxpr):
    """(column scatters, word scatters) on the pool's words in a jaxpr,
    conditionals' branches included once (the duplicate test's second
    sort holds none)."""
    col = word = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("scatter"):
            shape = eqn.invars[0].aval.shape
            col += shape == (WORDS, SLOTS)
            word += shape == (SIZE,)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            c, w = _pool_scatters(sub)
            col, word = col + c, word + w
    return col, word


def _on_the_view(prog, take, resv=RESV):
    """(the pool after, what was read, the trace's counts) of `prog` on
    a BlobPoolView under jit, flushed where engine._make_branch does."""
    counts = {}

    def go(pool, take, resv, x):
        view = BlobPoolView(*pool, jnp.int32(BASE), take, resv)
        out = prog(Context(jnp.int32(0), 1, blob=view), x, jnp)
        view.flush()
        counts.update(allocs=view.claims, folded=view.sets_folded,
                      alone=view.sets_alone, open=len(view.columns))
        return ((view.data, view.used, view.len_, view.gen, view.fail,
                 view.n_alloc, view.n_free), out)

    start = NumpyHeap(take, resv)
    args = ((start.data, start.used, start.len_, start.gen), take, resv,
            INPUTS)
    args = jax.tree.map(jnp.asarray, args)
    counts["scatters"] = _pool_scatters(jax.make_jaxpr(go)(*args).jaxpr)
    pool, out = jax.jit(go)(*args)
    return [np.asarray(a) for a in pool], \
        {k: np.asarray(v) for k, v in out.items()}, counts


@pytest.mark.parametrize("taken", list(TAKES))
@pytest.mark.parametrize("prog", list(PROGRAMS), ids=lambda p: p.__name__)
def test_a_behaviours_pool_is_the_numpy_statements(prog, taken):
    take = TAKES[taken]
    model = NumpyHeap(take, RESV)
    want = prog(model, INPUTS, np)
    (data, used, len_, gen, fail, n_alloc, n_free), got, counts = \
        _on_the_view(prog, take)
    assert np.array_equal(data, model.data), (data.reshape(WORDS, SLOTS),
                                              model.data.reshape(WORDS, -1))
    assert np.array_equal(used, model.used)
    assert np.array_equal(len_, model.len_)
    assert np.array_equal(gen, model.gen)
    assert (bool(fail), int(n_alloc), int(n_free)) == (
        model.fail, model.n_alloc, model.n_free)
    assert sorted(got) == sorted(want)
    for k in want:
        assert np.array_equal(got[k], want[k]), k
    columns, words, allocs, folded, alone = PROGRAMS[prog]
    assert counts == {"scatters": (columns, words), "allocs": allocs,
                      "folded": folded, "alone": alone, "open": 0}


def test_the_cases_do_what_their_names_say():
    """The NumPy side alone: the pool started full of other words, so a
    fresh slot's unset words are zeros that something wrote; the
    exhausted lanes failed and wrote nothing; the forged handle named
    the fresh slot."""
    model = NumpyHeap(TAKE, RESV)
    _partial_fill(model, INPUTS, np)
    fresh = (RESV[0] - BASE)[TAKE]
    words = model.data.reshape(WORDS, SLOTS)
    assert (words[[1, 3]][:, fresh] == 0).all() and (words[0, fresh] != 0).all()
    assert (np.delete(words, fresh, axis=1)
            == np.delete(np.arange(1000, 1000 + SIZE).reshape(WORDS, SLOTS),
                         fresh, axis=1)).all()
    model = NumpyHeap(TAKE, RESV)
    got = _pool_exhausted_in_some_lanes(model, INPUTS, np)
    assert model.fail and model.n_alloc == 3 * TAKE.sum() - 2
    assert (got["got"][[2, 5]] == 0).all() and (got["len"][[2, 5]] == 0).all()
    model = NumpyHeap(TAKE, RESV)
    got = _a_forged_handle_reads_between(model, INPUTS, np)
    assert np.array_equal(got["seen"][TAKE], INPUTS["v"][TAKE])
    assert (got["unset"] == 0).all()


def test_two_lanes_handed_one_slot_leave_one_lanes_column():
    """A free list that names a slot twice (no honest program can make
    one): the flush's keys are not unique, the lowest lane keeps the
    slot whole and the other's column is dropped — the scatter is still
    handed strictly ascending keys."""
    resv = RESV.copy()
    resv[0, 6] = resv[0, 1]
    take = np.ones(L, bool)
    (data, *_), _, _ = _on_the_view(_full_fill, take, resv)
    words = data.reshape(WORDS, SLOTS)
    slot = resv[0, 1] - BASE
    assert np.array_equal(words[:, slot], INPUTS["v"][1] + np.arange(WORDS))
    others = [lane for lane in range(L) if lane not in (1, 6)]
    for lane in others:
        assert np.array_equal(words[:, resv[0, lane] - BASE],
                              INPUTS["v"][lane] + np.arange(WORDS))
    untouched = np.setdiff1d(np.arange(SLOTS), resv[0] - BASE)
    assert np.array_equal(
        words[:, untouched],
        np.arange(1000, 1000 + SIZE).reshape(WORDS, SLOTS)[:, untouched])


# --- through the runtime: on one shard, and after a move -----------------

@actor
class Reader:
    words: I32
    sum_: I32
    len_: I32
    seen: I32

    @behaviour
    def take(self, st, payload: Blob):
        got = [self.blob_get(payload, w) for w in range(4)]
        ln = self.blob_length(payload)
        self.blob_free(payload)
        packed = got[0] | (got[1] << 8) | (got[2] << 16) | (got[3] << 24)
        return {**st, "words": packed, "sum_": sum(got), "len_": ln,
                "seen": st["seen"] + 1}


@actor
class Maker:
    out: Ref
    MAX_BLOBS = 1

    @behaviour
    def partly(self, st, v: I32):
        h = self.blob_alloc(length=3)
        self.blob_set(h, 1, v)
        self.blob_set(h, 2, v + 1, when=v > 100)
        self.send(st["out"], Reader.take, h)
        return st

    @behaviour
    def dirty(self, st, v: I32):
        # fills a payload with 0x7f words and frees it: the slot the
        # next alloc is handed holds them
        h = self.blob_alloc()
        for w in range(4):
            self.blob_set(h, w, 0x7f)
        self.blob_free(h)
        return st


@pytest.mark.parametrize("shards", [1, 2])
def test_unset_words_read_zero_at_the_receiver(shards):
    """A partly filled payload, sent: the receiver reads 0 in every word
    no set covered, though the slot held another payload's words before
    — on the maker's shard, and after the payload moved with its
    message to another."""
    opts = RuntimeOptions(mailbox_cap=4, batch=2, max_sends=1, msg_words=1,
                          inject_slots=8, blob_slots=4, blob_words=4,
                          **({"mesh_shards": 2} if shards == 2 else {}))
    rt = Runtime(opts)
    rt.declare(Maker, 2).declare(Reader, 2).start()
    r0 = rt.spawn(Reader, words=0, sum_=0, len_=0, seen=0)   # shard 0
    r1 = rt.spawn(Reader, words=0, sum_=0, len_=0, seen=0)   # shard 1 of 2
    reader = r1 if shards == 2 else r0
    maker = rt.spawn(Maker, out=reader)                      # shard 0
    for _ in range(4):                                       # every slot
        rt.send(maker, Maker.dirty, 0)
        rt.run(max_steps=4)
    assert rt.blobs_in_use == 0
    rt.send(maker, Maker.partly, 9)
    rt.run(max_steps=10)
    got = rt.state_of(reader)
    assert (got["words"], got["sum_"], got["len_"], got["seen"]) == (
        9 << 8, 9, 3, 1)
    rt.send(maker, Maker.partly, 101)
    rt.run(max_steps=10)
    got = rt.state_of(reader)
    assert (got["words"], got["sum_"], got["seen"]) == (
        (101 << 8) | (102 << 16), 203, 2)
    assert rt.blobs_in_use == 0
    assert rt.counter("n_blob_moved") == (2 if shards == 2 else 0)
    assert rt.counter("n_blob_remote") == 0
    assert engine.born_full(rt.program, rt.opts) == {
        "Maker": {"allocs": 2, "sets_folded": 6, "sets_alone": 0,
                  "windows": 0, "gets_windowed": 0, "gets_alone": 0},
        "Reader": {"allocs": 0, "sets_folded": 0, "sets_alone": 0,
                   "windows": 1, "gets_windowed": 4, "gets_alone": 0}}
    rt.stop()


# --- the compiled programs ------------------------------------------------

def _heap_rows(hlo, kinds=("scatter",)):
    from ponyc_tpu import costs
    return [r for r in costs.hlo_symbols(hlo) if r["kind"] in kinds
            and (r["scope"] or "").startswith("dispatch/heap")]


def _elements(shape):
    """How many elements an HLO shape (`s32[448,32]{1,0}`) holds."""
    dims = re.match(r"\w+\[([\d,]*)\]", shape).group(1)
    return int(np.prod([int(d) for d in dims.split(",") if d]))


def test_the_stencils_point_lowers_to_three_column_scatters_a_slot():
    """`taskbench-stencil`'s Point at 64 points: its 96 sets and three
    zeroings a batch slot are three scatters on the pool under
    `dispatch/heap/set` (the scan's body is compiled once), each of one
    index a lane, and `dispatch/heap/alloc` holds no scatter of
    W x lanes indices: what it writes is the slot's books, a lane each."""
    width = 64
    rt = _hlo._bench_rt("taskbench-stencil", "payload", width)
    words, slots = rt.opts.blob_words, rt.opts.blob_slots
    assert engine.born_full(rt.program, rt.opts) == {
        "Point": {"allocs": 3, "sets_folded": 96, "sets_alone": 0,
                  "windows": 1, "gets_windowed": 32, "gets_alone": 0}}
    jaxpr, hlo = _hlo.window_texts(rt)
    rt.stop()
    # the traced program: three scatters on the pool seen as [W, slots]
    # and none on the flat pool
    assert len(re.findall(rf"i32\[{words},{slots}\] = scatter\[", jaxpr)) == 3
    assert not re.findall(rf"i32\[{words * slots}\] = scatter", jaxpr)
    # the compiled one (the compiler may turn the pool round: by size)
    rows = _heap_rows(hlo)
    on_pool = [r for r in rows if _elements(r["shape"]) == words * slots]
    assert [(r["scope"], r["index_count"]) for r in on_pool] == [
        ("dispatch/heap/set", width)] * 3
    alloc = [r for r in rows if r["scope"] == "dispatch/heap/alloc"]
    assert alloc and all(r["index_count"] == width
                         and _elements(r["shape"]) == slots for r in alloc)
    assert {r["scope"] for r in _heap_rows(hlo, ("scatter", "gather"))} >= {
        "dispatch/heap/set", "dispatch/heap/alloc", "dispatch/heap/get",
        "dispatch/heap/free"}


def test_the_gups_window_opens_no_column():
    """The control: GUPS's Updater sets a traced word of a pinned
    handle and allocates nothing, so its one set a message goes the
    eager way — `ordered`'s sort and the single-word scatter on the
    flat pool — and no operation of the window sees the pool as
    [W, slots]."""
    rt = _hlo._bench_rt("gups-hpcc", "stream", 2048)
    words, slots = rt.opts.blob_words, rt.opts.blob_slots
    assert engine.born_full(rt.program, rt.opts) == {
        "Updater": {"allocs": 0, "sets_folded": 0, "sets_alone": 1,
                    "windows": 0, "gets_windowed": 0, "gets_alone": 1},
        "Streamer": dict.fromkeys(engine.POOL_FACTS, 0)}
    jaxpr, hlo = _hlo.window_texts(rt)
    rt.stop()
    assert f"[{words},{slots}]" not in jaxpr and f"[{words},{slots}]" not in hlo
    sets = [r for r in _heap_rows(hlo)
            if r["scope"] == "dispatch/heap/set"]
    assert len(sets) == 1 and f"s32[{words * slots}]" in sets[0]["shape"]


def test_the_dump_says_how_payloads_are_born():
    from ponyc_tpu import analysis
    rt = _hlo._bench_rt("taskbench-stencil", "payload", 8, analysis=1)
    assert rt.profile()["cohorts"]["Point"]["born_full"] == {
        "allocs": 3, "sets_folded": 96, "sets_alone": 0,
        "windows": 1, "gets_windowed": 32, "gets_alone": 0}
    text = analysis.attach(rt).dump(out=io.StringIO())
    rt.stop()
    assert re.search(r"cohort Point: .* pinned_handles=- born_full=3/96/0/1/32/0",
                     text)
