"""`Context.blob_set` orders its lanes before it writes them.

The heap's write is a scatter that XLA is told is sorted and unique
(`api.BlobPoolView.ordered`): on a TPU the scatter of single words is
one update after another unless both are declared. Both statements are
made true by the runtime, so they are held here on the lanes that could
break them: `blob_set` against its semantics written out in NumPy, the
key vector strictly ascending in every case, and a forged duplicate
handle — the one breach iso ownership cannot rule out while an untyped
int may carry a handle — leaving exactly one of its values in the word.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ponyc_tpu import Blob, I32, Runtime, RuntimeOptions, actor, behaviour
from ponyc_tpu.api import BlobPoolView, Context
from ponyc_tpu.ops import pack

SLOTS, WORDS, BASE = 8, 4, 16
SIZE = SLOTS * WORDS
FREED, STALE = 5, 6           # slot 5 is not in use, slot 6 lives its 2nd life
GEN = np.array([1, 1, 1, 1, 1, 1, 2, 1], np.int32)
USED = np.arange(SLOTS) != FREED


def _h(slot, gen=None):
    """The handle of local slot `slot` (this shard's handles start at
    BASE), at the slot's own generation unless told otherwise."""
    return int(pack.blob_handle(BASE + slot, GEN[slot] if gen is None
                                else gen))


def _pool():
    return np.arange(1000, 1000 + SIZE, dtype=np.int32)


def _numpy_blob_set(data, take, h, i, v, when):
    """What a blob_set means, one lane at a time: the word is written
    iff the lane was taken, `when` holds, the handle is this shard's,
    of the slot's current generation and in use, and the word exists."""
    out = data.copy()
    shape = np.broadcast_shapes(*(np.shape(x) for x in (take, h, i, v, when)))
    lanes = [np.broadcast_to(x, shape).reshape(-1)
             for x in (take, h, i, v, when)]
    wrote = []
    for t, hh, ii, vv, ww in zip(*lanes):
        slot = int(pack.blob_slot(int(hh))) - BASE
        if not (t and ww and hh >= 0 and 0 <= slot < SLOTS
                and GEN[slot] == int(pack.blob_gen_of(int(hh)))
                and USED[slot] and 0 <= ii < WORDS):
            continue
        out[ii * SLOTS + slot] = vv
        wrote.append(ii * SLOTS + slot)
    return out, wrote


def _blob_set(take, h, i, v, when):
    """(the pool after, the keys the scatter was handed) of one
    `ctx.blob_set` under jit, the keys read out of the helper."""
    keys = []
    ordered = BlobPoolView.ordered

    def spy(self, *args):
        key, value = ordered(self, *args)
        keys.append(key)
        return key, value

    @jax.jit
    def write(data, take, h, i, v, when):
        view = BlobPoolView(data, jnp.asarray(USED), jnp.full(SLOTS, WORDS),
                            jnp.asarray(GEN), jnp.int32(BASE), take, None)
        Context(jnp.int32(0), 1, blob=view).blob_set(h, i, v, when)
        return view.data, keys.pop()

    BlobPoolView.ordered = spy
    try:
        return [np.asarray(x) for x in write(
            _pool(), *(jnp.asarray(x) for x in (take, h, i, v, when)))]
    finally:
        BlobPoolView.ordered = ordered


L = 8
ALL = np.ones(L, bool)
OWN = np.array([_h(s) for s in (3, 0, 7, 1, 4, 2, STALE, 3)], np.int32)
VALUES = np.arange(L, dtype=np.int32) * 11 + 7
CASES = {
    # name: (take, handles, word, value, when)
    "every-lane-writes": (ALL, OWN, np.arange(L) % WORDS, VALUES, True),
    "dropped-between-writing": (np.array([1, 0, 1, 0, 0, 1, 1, 0], bool),
                                np.r_[OWN[:7], _h(1)], 2, VALUES, True),
    "when-false": (ALL, OWN, np.arange(L) % WORDS, VALUES,
                   np.array([1, 1, 0, 1, 0, 0, 1, 1], bool)),
    "null-handles": (ALL, np.array([-1, _h(0), -1, _h(1), -1, -1, _h(2), -1]),
                     1, VALUES, True),
    "stale-generation": (ALL, np.array(
        [_h(STALE, gen=1), _h(0), _h(STALE), _h(1, gen=3), _h(2), _h(3, gen=0),
         _h(4), _h(7)]), 3, VALUES, True),
    "handles-out-of-range": (ALL, np.array(
        [int(pack.blob_handle(BASE - 1, 1)), _h(0),
         int(pack.blob_handle(BASE + SLOTS, 1)), _h(1),
         int(pack.blob_handle(0, 1)), int(pack.blob_handle(BASE + 200, 1)),
         _h(2), _h(3)]), 0, VALUES, True),
    "freed-slot": (ALL, np.array([_h(FREED), _h(0), _h(FREED), _h(1), _h(2),
                                  _h(3), _h(4), _h(7)]), 2, VALUES, True),
    "word-out-of-range": (ALL, np.r_[OWN[:7], _h(1)],
                          np.array([-1, 0, WORDS, 3, 2**30, -2**31, 1, 2]),
                          VALUES, True),
    "words-by-lanes-index": (ALL[:4], np.array([_h(0), -1, _h(FREED), _h(7)]),
                             np.arange(WORDS)[:, None],
                             np.arange(WORDS * 4, dtype=np.int32)
                             .reshape(WORDS, 4) - 5, True),
    "all-lanes-dropped": (~ALL, np.r_[OWN[:7], _h(1)], 1, VALUES, True),
    "nothing-taken-nothing-valid": (ALL, np.full(L, -1), 1, VALUES, False),
    "one-lane": (ALL[:1], np.array([_h(4)]), 3, np.array([99], np.int32),
                 True),
    "one-lane-dropped": (ALL[:1], np.array([_h(FREED)]), 3,
                         np.array([99], np.int32), True),
}


@pytest.mark.parametrize("case", CASES)
def test_blob_set_is_its_numpy_statement_and_its_keys_ascend(case):
    take, h, i, v, when = CASES[case]
    h, i = np.asarray(h, np.int32), np.asarray(i, np.int32)
    want, wrote = _numpy_blob_set(_pool(), take, h, i, v, when)
    assert len(set(wrote)) == len(wrote), "the case itself keeps iso"
    got, keys = _blob_set(take, h, i, v, when)
    assert np.array_equal(got, want)
    lanes = np.broadcast_shapes(*(np.shape(x) for x in (take, h, i, v)))
    assert keys.dtype == np.uint32 and keys.shape == (int(np.prod(lanes)),)
    assert (np.diff(keys.astype(np.int64)) > 0).all(), keys
    assert sorted(wrote) == keys[keys < SIZE].tolist()
    if case == "every-lane-writes":
        assert not np.array_equal(got, _pool())


# A breach of iso: lanes that hold one handle (forged or copied through
# an untyped int) and write one word. (lanes of the breach, word) first,
# then what the other lanes do.
BREACHES = {
    "two-lanes-one-word": ([(2, 3)], "write"),
    "three-lanes-one-word": ([(1, 4, 6)], "write"),
    "two-breaches": ([(0, 1), (5, 6, 7)], "write"),
    # the breach is first in the sorted order, and a dropped lane sits
    # at the index a re-keyed lane takes: every key past the end is
    # made anew when the vector is sorted the second time
    "beside-dropped-lanes": ([(2, 3)], "drop"),
    "every-lane-one-word": ([tuple(range(L))], "write"),
}


@pytest.mark.parametrize("case", BREACHES)
def test_a_forged_duplicate_leaves_one_of_its_values(case):
    breaches, others = BREACHES[case]
    h = np.array([_h(s) for s in (0, 1, 2, 3, 4, 7, 0, 1)], np.int32)
    i = np.array([3, 3, 2, 2, 1, 1, 0, 0], np.int32)
    take = np.ones(L, bool)
    in_breach = {lane for b in breaches for lane in b}
    for b in breaches:
        h[list(b)], i[list(b)] = _h(2), b[0] % WORDS
    if case == "beside-dropped-lanes":
        h[[2, 3]], i[[2, 3]] = _h(0), 0          # flat index 0: sorts first
    if others == "drop":
        take = np.array([lane in in_breach for lane in range(L)])
    v = VALUES + 500
    honest = np.array([lane not in in_breach for lane in range(L)]) & take
    want, _ = _numpy_blob_set(_pool(), honest, h, i, v, True)
    got, keys = _blob_set(take, h, i, v, True)
    assert (np.diff(keys.astype(np.int64)) > 0).all(), keys
    for b in breaches:
        word = int(i[b[0]]) * SLOTS + int(pack.blob_slot(int(h[b[0]]))) - BASE
        assert got[word] in v[list(b)], (word, got[word])
        assert (keys == word).sum() == 1
        want[word] = got[word]
    assert np.array_equal(got, want), "every other word is as it was"
    assert (keys < SIZE).sum() == honest.sum() + len(breaches)


def test_two_actors_with_one_forged_handle_write_one_word():
    """Through the runtime: the owner of a blob and an actor that
    carries the same handle in an untyped I32 field write the same word
    in the same tick. One of the two values is in the word, the blob's
    other words and the other blobs are as they were, and no counter
    moved."""
    @actor
    class Forger:
        keep: Blob
        h: I32

        @behaviour
        def poke(self, st, v: I32):
            self.blob_set(st["h"], 1, v)
            return st

    rt = Runtime(RuntimeOptions(mailbox_cap=4, batch=2, max_sends=1,
                                msg_words=1, inject_slots=8, blob_slots=8,
                                blob_words=4))
    rt.declare(Forger, 4).start()
    mine, other = rt.blob_store([1, 2, 3, 4]), rt.blob_store([5, 6, 7, 8])
    a = rt.spawn(Forger, keep=mine, h=mine)
    b = rt.spawn(Forger, keep=-1, h=mine)
    c = rt.spawn(Forger, keep=other, h=other)
    rt.send(a, Forger.poke, 111)
    rt.send(b, Forger.poke, 222)
    rt.send(c, Forger.poke, 333)
    assert rt.run(max_steps=4) == 0
    got = rt.blob_fetch(mine).tolist()
    assert got[1] in (111, 222) and got[0] == 1 and got[2:] == [3, 4], got
    assert rt.blob_fetch(other).tolist() == [5, 333, 7, 8]
    for name in ("n_badmsg", "n_deadletter", "n_rejected", "n_blob_remote"):
        assert rt.counter(name) == 0, name
    rt.stop()
