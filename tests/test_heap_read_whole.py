"""A payload is read whole: the first `blob_get` of a handle at a static
word gathers the rows its behaviour reads in ONE operation, the later
ones read the result.

`Context.blob_get` of a handle object at a Python or NumPy integer word
opens a *read window* (`api.BlobPoolView.windowed`) where the
behaviour's own probe priced one (`read_plan` / `window_rows`): one
gather of [rows, lanes] from the pool's first rows seen as [rows,
nslots]. Whatever
writes the pool, `gen` or `used` closes every window. Held here: every
way a behaviour can mix reads with sets, allocs and frees against the
pool read out in NumPy one lane at a time, over a pool that starts full
of other words and handles that are null, stale, freed, forged, another
shard's or two lanes' alias of one slot; how many window and single-word
gathers each mix lowers to and what the trace counts; a probe and a
trace that disagree; a frozen payload read by two lanes through the
runtime; and the stencil's and GUPS's compiled windows.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ponyc_tpu import (BlobVal, I32, Ref, Runtime, RuntimeOptions, actor,
                       behaviour)
from ponyc_tpu import api
from ponyc_tpu.api import BlobPoolView, Context
from ponyc_tpu.ops import pack
from ponyc_tpu.runtime import engine

import _hlo
from test_heap_born_full import _elements, _heap_rows

L, SLOTS, WORDS, BASE = 8, 64, 16, 64       # 8 slots a lane
SIZE = SLOTS * WORDS
LANE = np.arange(L, dtype=np.int32)
GEN0 = (np.arange(SLOTS, dtype=np.int32) * 5) % 7
USED0 = np.ones(SLOTS, bool)
USED0[[6, 21, 22, 23, 24, 25]] = False        # 6: freed; 21..25: free
LEN0 = np.where(USED0, 1 + np.arange(SLOTS) % WORDS, 0).astype(np.int32)
# a lane's reservation window (one site): lanes 0..4 a free slot each,
# lanes 5..7 found the free list empty
RESV = np.full((1, L), -1, np.int32)
RESV[0, :5] = BASE + np.arange(21, 26)
TAKE = np.array([1, 1, 1, 0, 1, 1, 1, 1], bool)


def _handle(slot, gen=None):
    return int(pack.blob_handle(BASE + slot,
                                GEN0[slot] if gen is None else gen))


# h: lane 0 live, 1 null, 2 stale (another generation), 3 freed (slot 6
# is not in use), 4 forged (a slot past this shard's), 5 another shard's
# (a slot below this shard's first), 6 and 7 live.
H = np.array([_handle(9), -1, _handle(3, (GEN0[3] + 1) & pack.BLOB_GEN_MASK),
              _handle(6), int(pack.blob_handle(BASE + SLOTS + 2, 1)),
              int(pack.blob_handle(BASE - 5, 1)), _handle(30), _handle(0)],
             np.int32)
H2 = np.array([_handle(s) for s in (4, 15, 1, 27, 2, 8, 13, 7)], np.int32)
ALIAS = np.array([_handle(s) for s in (11, 12, 11, 14, 12, 11, 16, 17)],
                 np.int32)                    # lanes 0, 2, 5 and 1, 4


class NumpyPool:
    """The pool's semantics, one lane at a time, in program order: what
    `Context.blob_*` mean. It starts full of other words, most slots in
    use; an alloc zeroes its slot's words at once."""

    def __init__(self, take):
        self.data = np.arange(1000, 1000 + SIZE, dtype=np.int32)
        self.used, self.len_, self.gen = (USED0.copy(), LEN0.copy(),
                                          GEN0.copy())
        self.take, self.site = take, 0

    def _lanes(self, *xs):
        return [np.broadcast_to(np.asarray(x), (L,)) for x in xs]

    def _slot(self, h):
        slot = int(pack.blob_slot(int(h))) - BASE
        ok = (h >= 0 and 0 <= slot < SLOTS
              and self.gen[slot] == int(pack.blob_gen_of(int(h))))
        return slot, ok

    def blob_alloc(self, length=None, when=True):
        row, self.site = RESV[self.site], self.site + 1
        (when,) = self._lanes(when)
        h = np.full(L, -1, np.int32)
        for lane in range(L):
            if not (when[lane] and self.take[lane]) or row[lane] < 0:
                continue
            slot = row[lane] - BASE
            self.gen[slot] = (self.gen[slot] + 1) & pack.BLOB_GEN_MASK
            self.used[slot] = True
            self.len_[slot] = WORDS if length is None else length
            self.data[slot::SLOTS] = 0
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


# --- the programs: (ctx, inputs, xp) -> {name: lanes read} ---------------

def _every_word(ctx, x, xp):
    return {f"w{w}": ctx.blob_get(x["h"], w) for w in range(WORDS)}


def _a_subset_over_the_break_even(ctx, x, xp):
    return {f"w{w}": ctx.blob_get(x["h2"], w) for w in (7, 2, 4, 3, 6, 5)}


def _a_subset_under_the_break_even(ctx, x, xp):
    return {"first": ctx.blob_get(x["h2"], 0),
            "last": ctx.blob_get(x["h2"], WORDS - 1)}


def _one_static_word(ctx, x, xp):
    return {"w3": ctx.blob_get(x["h2"], 3)}


def _a_traced_index_between_static_ones(ctx, x, xp):
    a = ctx.blob_get(x["h2"], 0)
    t = ctx.blob_get(x["h2"], x["w"])
    return {"a": a, "t": t, "b": ctx.blob_get(x["h2"], np.int32(1))}


def _static_words_out_of_range(ctx, x, xp):
    return {"a": ctx.blob_get(x["h2"], 0), "past": ctx.blob_get(x["h2"], WORDS),
            "neg": ctx.blob_get(x["h2"], -1), "b": ctx.blob_get(x["h2"], 1)}


def _get_set_get_at_a_static_word(ctx, x, xp):
    a, b = ctx.blob_get(x["h2"], 0), ctx.blob_get(x["h2"], 1)
    ctx.blob_set(x["h2"], 0, a + b + 5, when=x["go"])
    return {"a": a, "b": b, "a2": ctx.blob_get(x["h2"], 0),
            "b2": ctx.blob_get(x["h2"], 1)}


def _get_set_get_at_a_traced_word(ctx, x, xp):
    a, b = ctx.blob_get(x["h2"], 0), ctx.blob_get(x["h2"], 1)
    ctx.blob_set(x["h2"], x["w"], a - b)        # names word 0 or 1 in some
    return {"a": a, "b": b, "a2": ctx.blob_get(x["h2"], 0),
            "b2": ctx.blob_get(x["h2"], 1)}


def _get_alloc_and_fill_get(ctx, x, xp):
    a, b = ctx.blob_get(x["h2"], 2), ctx.blob_get(x["h2"], 3)
    n = ctx.blob_alloc(when=x["go"])
    ctx.blob_set(n, 2, a + 1)
    ctx.blob_set(n, 3, b + 1)
    return {"a": a, "b": b, "a2": ctx.blob_get(x["h2"], 2),
            "b2": ctx.blob_get(x["h2"], 3), "n2": ctx.blob_get(n, 2),
            "n3": ctx.blob_get(n, 3), "n4": ctx.blob_get(n, 4)}


def _two_handles_read_interleaved(ctx, x, xp):
    out = {}
    for w in range(4):
        out[f"h{w}"] = ctx.blob_get(x["h"], w)
        out[f"g{w}"] = ctx.blob_get(x["h2"], w + 1)
    return out


def _an_alias_in_two_lanes(ctx, x, xp):
    return {f"w{w}": ctx.blob_get(x["alias"], w) for w in range(WORDS)}


def _a_partly_filled_payload(ctx, x, xp):
    n = ctx.blob_alloc(length=3)
    ctx.blob_set(n, 1, x["v"])
    ctx.blob_set(n, 2, x["v"] * 3, when=x["go"])
    return {f"w{w}": ctx.blob_get(n, w) for w in range(WORDS)}


def _a_free_between_two_reads_of_an_alias(ctx, x, xp):
    # `alias2` holds h2's values and is another object: the free of h2
    # must show in its second read
    a, b = ctx.blob_get(x["alias2"], 0), ctx.blob_get(x["alias2"], 1)
    ctx.blob_free(x["h2"], when=x["go"])
    return {"a": a, "b": b, "a2": ctx.blob_get(x["alias2"], 0),
            "b2": ctx.blob_get(x["alias2"], 1)}


def _a_length_closes_nothing(ctx, x, xp):
    a = ctx.blob_get(x["h"], 0)
    ln = ctx.blob_length(x["h"])
    return {"a": a, "ln": ln, "b": ctx.blob_get(x["h"], 1)}


def _a_copy_of_the_handle_is_another_object(ctx, x, xp):
    return {f"w{w}": ctx.blob_get(xp.where(x["go"] | True, x["h2"], -1), w)
            for w in range(3)}


INPUTS = {
    "h": H, "h2": H2, "alias": ALIAS, "alias2": H2.copy(),
    "v": LANE * 11 + 7,
    "go": np.array([1, 0, 1, 1, 0, 1, 1, 0], bool),
    "w": np.array([0, 1, 2, 15, 0, 16, -1, 1], np.int32),
}
# program: (window gathers, single-word gathers of the pool's words,
#           windows, gets windowed, gets alone)
PROGRAMS = {
    _every_word: (1, 0, 1, WORDS, 0),
    _a_subset_over_the_break_even: (1, 0, 1, 6, 0),
    _a_subset_under_the_break_even: (0, 2, 0, 0, 2),
    _one_static_word: (0, 1, 0, 0, 1),
    _a_traced_index_between_static_ones: (1, 1, 1, 2, 1),
    _static_words_out_of_range: (1, 2, 1, 2, 2),
    _get_set_get_at_a_static_word: (2, 0, 2, 4, 0),
    _get_set_get_at_a_traced_word: (2, 0, 2, 4, 0),
    _get_alloc_and_fill_get: (3, 0, 3, 7, 0),
    _two_handles_read_interleaved: (2, 0, 2, 8, 0),
    _an_alias_in_two_lanes: (1, 0, 1, WORDS, 0),
    _a_partly_filled_payload: (1, 0, 1, WORDS, 0),
    _a_free_between_two_reads_of_an_alias: (2, 0, 2, 4, 0),
    _a_length_closes_nothing: (1, 0, 1, 2, 0),
    _a_copy_of_the_handle_is_another_object: (0, 3, 0, 0, 3),
}
TAKES = {"some-lanes-taken": TAKE, "every-lane-taken": np.ones(L, bool)}


def _pool_gathers(jaxpr):
    """({rows of a window gather: how many}, single-word gathers) on the
    pool's words in a jaxpr."""
    windows, words = {}, 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            shape = eqn.invars[0].aval.shape
            if len(shape) == 2 and shape[1] == SLOTS:
                assert eqn.outvars[0].aval.shape == (shape[0], L)
                windows[shape[0]] = windows.get(shape[0], 0) + 1
            words += shape == (SIZE,)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            w, s = _pool_gathers(sub)
            words += s
            for rows, n in w.items():
                windows[rows] = windows.get(rows, 0) + n
    return windows, words


def _on_the_view(prog, take):
    """(the pool after, what was read, the trace's counts) of `prog` on
    a BlobPoolView under jit, the way the engine traces a behaviour:
    once abstractly on a view that records, then on one that follows
    the plan the first made."""
    seen = {}

    def go(plan, pool, take, resv, x):
        view = BlobPoolView(*pool, jnp.int32(BASE), take, resv, reads=plan)
        out = prog(Context(jnp.int32(0), 1, blob=view), x, jnp)
        view.flush()
        seen.update(view.read_facts(), plan=view.read_plan(),
                    open=len(view.columns))
        return (view.data, view.used, view.len_, view.gen), out

    start = NumpyPool(take)
    args = jax.tree.map(jnp.asarray, (
        (start.data, start.used, start.len_, start.gen), take, RESV, INPUTS))
    jax.eval_shape(functools.partial(go, ()), *args)
    plan = seen["plan"]
    probed = dict(seen)
    seen["gathers"] = _pool_gathers(
        jax.make_jaxpr(functools.partial(go, plan))(*args).jaxpr)
    assert {k: seen[k] for k in probed} == probed     # the trace's own
    pool, out = jax.jit(functools.partial(go, plan))(*args)
    return [np.asarray(a) for a in pool], \
        {k: np.asarray(v) for k, v in out.items()}, seen


@pytest.mark.parametrize("taken", list(TAKES))
@pytest.mark.parametrize("prog", list(PROGRAMS), ids=lambda p: p.__name__)
def test_a_behaviours_reads_are_the_numpy_statements(prog, taken):
    take = TAKES[taken]
    model = NumpyPool(take)
    want = prog(model, INPUTS, np)
    (data, used, len_, gen), got, seen = _on_the_view(prog, take)
    assert np.array_equal(data, model.data)
    assert np.array_equal(used, model.used)
    assert np.array_equal(len_, model.len_)
    assert np.array_equal(gen, model.gen)
    assert sorted(got) == sorted(want)
    for k in want:
        assert np.array_equal(got[k], want[k]), (k, got[k], want[k])
    gathers, words, windows, windowed, alone = PROGRAMS[prog]
    assert (sum(seen["gathers"][0].values()), seen["gathers"][1]) == (
        gathers, words)
    assert {k: seen[k] for k in ("windows", "gets_windowed", "gets_alone",
                                 "open")} == {
        "windows": windows, "gets_windowed": windowed, "gets_alone": alone,
        "open": 0}
    # a window holds the pool's rows up to the last one read, no more
    assert sorted(r for r, n in seen["gathers"][0].items()
                  for _ in range(n)) == sorted(filter(None, seen["plan"]))


def test_the_cases_do_what_their_names_say():
    """The NumPy side alone: the bad handles read 0 in their lanes and
    the live ones real words; the aliased lanes read one slot; the set,
    the alloc and the free between two reads showed in the second."""
    take = np.ones(L, bool)
    got = _every_word(NumpyPool(take), INPUTS, np)
    for w in range(WORDS):
        assert (got[f"w{w}"][[1, 2, 3, 4, 5]] == 0).all()
        assert np.array_equal(got[f"w{w}"][[0, 6, 7]],
                              1000 + w * SLOTS + np.array([9, 30, 0]))
    got = _an_alias_in_two_lanes(NumpyPool(take), INPUTS, np)
    assert (got["w5"][[0, 2, 5]] == 1000 + 5 * SLOTS + 11).all()
    assert (got["w5"][[1, 4]] == 1000 + 5 * SLOTS + 12).all()
    got = _get_set_get_at_a_static_word(NumpyPool(take), INPUTS, np)
    go = INPUTS["go"]
    assert np.array_equal(got["a2"][go], (got["a"] + got["b"] + 5)[go])
    assert np.array_equal(got["a2"][~go], got["a"][~go])
    got = _get_set_get_at_a_traced_word(NumpyPool(take), INPUTS, np)
    assert (got["a2"] != got["a"]).sum() == 2 and (
        got["b2"] != got["b"]).sum() == 2
    got = _a_partly_filled_payload(NumpyPool(take), INPUTS, np)
    fresh = RESV[0] >= 0
    assert np.array_equal(got["w1"][fresh], INPUTS["v"][fresh])
    assert all((got[f"w{w}"] == 0).all() for w in range(WORDS)
               if w not in (1, 2))
    got = _a_free_between_two_reads_of_an_alias(NumpyPool(take), INPUTS, np)
    assert (got["a2"][go] == 0).all() and np.array_equal(
        got["a2"][~go], got["a"][~go]) and (got["a"] != 0).all()
    got = _get_alloc_and_fill_get(NumpyPool(take), INPUTS, np)
    made = go & (RESV[0] >= 0)
    assert np.array_equal(got["n2"][made], got["a"][made] + 1)
    assert (got["n4"] == 0).all() and np.array_equal(got["a2"], got["a"])


@pytest.mark.parametrize("words, slots_a_lane, rows", [
    (range(32), 7, 32), ((0, 1), 7, 2), ((5, 4), 7, 6), ((3, 3, 3), 7, 4),
    (range(8, 24), 7, 24), ((0,), 7, None), ((7,), 7, None),
    ((0, 31), 7, None), ((0, 2047), 1, None), ((0, 100, 200, 400), 1, None),
    (range(32), 56, 32), ((0, 1, 2), 56, 3),
    (list(range(12)) + [31], 56, None)], ids=str)
def test_a_window_opens_where_it_is_priced_under_its_gets(words, slots_a_lane,
                                                          rows):
    """`window_rows`: the two measured constants against the gets a
    window stands for, at the stencil's 7 pool slots a lane, GUPS's 1
    and the 56 of a 470 MB pool. All of a 32-word payload, a pair of
    neighbours and a word read over and over open one, from the pool's
    first row to the last row read; a lone get, the two ends of a
    payload, a few words of a 2,048-word slice and a dozen gets whose
    window would span 470 MB do not."""
    assert api.window_rows(list(words), slots_a_lane) == rows


def test_a_window_costs_what_it_was_measured_to():
    """docs/DESIGN.md §7b's measurement, as the constants state it: at
    the stencil's pool a 32-row window is priced at a few single gets
    (it read 0.94 of one in the cell), never under the gather's fixed
    part."""
    whole = api.WINDOW_FIXED + 32 * 7 * api.WINDOW_A_WORD
    assert 1 <= whole <= 6
    assert 0.5 <= api.WINDOW_FIXED <= 1.5 and 0 < api.WINDOW_A_WORD < 0.1


# --- through the runtime --------------------------------------------------

_READS = [4]


@actor
class Fickle:
    seen: I32

    @behaviour
    def take(self, st, payload: BlobVal):
        n, _READS[0] = _READS[0], 2         # the probe reads 4, the trace 2
        got = [self.blob_get(payload, w) for w in range(n)]
        return {**st, "seen": st["seen"] + sum(got)}


def test_a_trace_that_reads_otherwise_than_its_probe_raises():
    rt = Runtime(RuntimeOptions(mailbox_cap=4, batch=1, max_sends=1,
                                msg_words=1, inject_slots=8, blob_slots=4,
                                blob_words=4))
    rt.declare(Fickle, 2).start()
    a = rt.spawn(Fickle, seen=0)
    _READS[0] = 4
    rt.send(a, Fickle.take, rt.blob_store([1, 2, 3, 4]))
    with pytest.raises(RuntimeError,
                       match="the probe and the trace disagree"):
        rt.run(max_steps=2)
    rt.stop()


@actor
class Summer:
    total: I32
    first: I32
    seen: I32

    @behaviour
    def take(self, st, payload: BlobVal):
        got = [self.blob_get(payload, w) for w in range(4)]
        return {**st, "total": st["total"] + sum(got), "first": got[0],
                "seen": st["seen"] + 1}


@actor
class Publisher:
    a: Ref
    b: Ref
    c: Ref
    MAX_BLOBS = 1
    MAX_SENDS = 3

    @behaviour
    def publish(self, st, v: I32):
        h = self.blob_alloc()
        for w in range(4):
            self.blob_set(h, w, v + w)
        shared = self.blob_freeze(h)
        for r in ("a", "b", "c"):
            self.send(st[r], Summer.take, shared)
        return st


def test_a_frozen_payload_is_read_whole_by_every_lane_that_holds_it():
    """One BlobVal sent to three readers of one cohort: the three lanes
    name one slot in the same dispatch, and each window holds its
    words."""
    rt = Runtime(RuntimeOptions(mailbox_cap=4, batch=2, max_sends=3,
                                msg_words=1, inject_slots=8, blob_slots=4,
                                blob_words=4))
    rt.declare(Publisher, 1).declare(Summer, 4).start()
    readers = [rt.spawn(Summer, total=0, first=0, seen=0) for _ in range(3)]
    pub = rt.spawn(Publisher, a=readers[0], b=readers[1], c=readers[2])
    rt.send(pub, Publisher.publish, 100)
    rt.run(max_steps=6)
    for r in readers:
        got = rt.state_of(r)
        assert (got["total"], got["first"], got["seen"]) == (406, 100, 1)
    assert engine.born_full(rt.program, rt.opts) == {
        "Publisher": {"allocs": 1, "sets_folded": 4, "sets_alone": 0,
                      "windows": 0, "gets_windowed": 0, "gets_alone": 0},
        "Summer": {"allocs": 0, "sets_folded": 0, "sets_alone": 0,
                   "windows": 1, "gets_windowed": 4, "gets_alone": 0}}
    rt.stop()


# --- the compiled programs ------------------------------------------------

# a gather a slot an index that fills, in a jaxpr's text -> its rows:
# a read window where they are the pool's (the flush's row gather
# clips; delivery's are four rows)
_WINDOW_EQN = (r"gather\[\s*dimension_numbers=GatherDimensionNumbers\("
               r"offset_dims=\(0,\), collapsed_slice_dims=\(1,\), "
               r"start_index_map=\(1,\)[^\]]*?"
               r"mode=GatherScatterMode.FILL_OR_DROP\s*"
               r"slice_sizes=\((\d+), 1\)")


def test_the_stencils_point_lowers_to_one_window_gather_a_slot():
    """`taskbench-stencil`'s Point at 64 points: the 32 gets of the
    payload it was sent are ONE gather of [32, lanes] on the pool under
    `dispatch/heap/get` (the scan's body is compiled once), and no
    gather of one word a lane reads the pool's words."""
    width = 64
    rt = _hlo._bench_rt("taskbench-stencil", "payload", width)
    words, slots = rt.opts.blob_words, rt.opts.blob_slots
    assert engine.born_full(rt.program, rt.opts)["Point"] == {
        "allocs": 3, "sets_folded": 96, "sets_alone": 0,
        "windows": 1, "gets_windowed": 32, "gets_alone": 0}
    jaxpr, hlo = _hlo.window_texts(rt)
    rt.stop()
    # the traced program: one window gather, [W, lanes] of it
    assert re.findall(_WINDOW_EQN, jaxpr).count(str(words)) == 1
    pool_reads = [r for r in _heap_rows(hlo, ("gather",))
                  if r["table_bytes"] == 4 * words * slots]
    assert [(r["scope"], r["index_count"], _elements(r["shape"]))
            for r in pool_reads] == [
        ("dispatch/heap/get", width, words * width)]


def test_the_gups_window_opens_no_read_window():
    """The control: GUPS's Updater reads a traced word of its table, so
    its one get a message gathers one word a lane from the flat pool
    and no operation of the window sees the pool as [W, slots]."""
    rt = _hlo._bench_rt("gups-hpcc", "stream", 2048)
    words, slots = rt.opts.blob_words, rt.opts.blob_slots
    facts = engine.born_full(rt.program, rt.opts)["Updater"]
    assert (facts["windows"], facts["gets_windowed"],
            facts["gets_alone"]) == (0, 0, 1)
    jaxpr, hlo = _hlo.window_texts(rt)
    rt.stop()
    assert f"[{words},{slots}]" not in jaxpr and f"[{words},{slots}]" not in hlo
    assert str(words) not in re.findall(_WINDOW_EQN, jaxpr)
