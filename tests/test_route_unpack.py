"""`route._route_unpack` — the received all-to-all buckets joined front to
front by contiguous copies, `_route_pack` run backwards — against the
plain NumPy form: keep the valid entries of the padded list in order,
pad to the short list's length. Every shard count, message width and
fill must give that form bit for bit, and the pack followed by the
unpack must hand back the outbox's valid entries by destination, then
in arrival order.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _hlo
from ponyc_tpu.runtime import route
from ponyc_tpu.runtime.delivery import Entries

L_IN = 48


def default_bucket(shards, l_in=L_IN):
    """`state.layout_sizes`' bucket for a shard that emits `l_in`."""
    return max(16, min(l_in, 4 * l_in // shards))


def received(fill, bucket, w1):
    """The exchanged buffers as a shard finds them: block d holds
    `fill[d]` entries at its front (every entry told apart), then
    -1 / -1 / 0."""
    shards = len(fill)
    slot = np.arange(shards * bucket, dtype=np.int32)
    valid = (slot % bucket) < np.repeat(np.asarray(fill), bucket)
    tgt = np.where(valid, 7 + 3 * slot, -1).astype(np.int32)
    sender = np.where(valid, 1000 + slot, -1).astype(np.int32)
    words = np.where(valid[None, :],
                     slot[None, :] * 8
                     + np.arange(w1, dtype=np.int32)[:, None] + 1,
                     0).astype(np.int32)
    return tgt, sender, words


def reference_unpack(tgt, sender, words, l_in):
    """The compaction, in NumPy: the valid entries in list order, then
    -1 / -1 / 0 up to `l_in`."""
    keep = np.flatnonzero(tgt >= 0)
    assert keep.shape[0] <= l_in
    pad = l_in - keep.shape[0]
    return (np.concatenate([tgt[keep], np.full(pad, -1, np.int32)]),
            np.concatenate([sender[keep], np.full(pad, -1, np.int32)]),
            np.concatenate([words[:, keep],
                            np.zeros((words.shape[0], pad), np.int32)],
                           axis=1))


def unpack(tgt, sender, words, fill, bucket, l_in):
    got = jax.jit(functools.partial(
        route._route_unpack, shards=len(fill), bucket=bucket, l_in=l_in))(
        Entries(jnp.asarray(tgt), jnp.asarray(sender), jnp.asarray(words)),
        jnp.asarray(fill, jnp.int32))
    return tuple(np.asarray(x) for x in got)


def fills(kind, shards, bucket, l_in):
    if kind == "empty":
        return [0] * shards
    if kind == "one_block_full":          # the rest came empty
        return [0] * (shards - 1) + [min(bucket, l_in)]
    if kind == "first_block_full":
        return [min(bucket, l_in)] + [0] * (shards - 1)
    if kind == "sum_is_l_in":             # uneven, to the entry
        out, left = [], l_in
        for d in range(shards):
            take = min(bucket, left if d == shards - 1
                       else max(0, left // 2 - d))
            out.append(take)
            left -= take
        out[0] += min(left, bucket - out[0])
        assert sum(out) == l_in, out
        return out
    if kind == "every_block_part_full":
        return [1 + (5 * d + 3) % min(bucket - 1, l_in // shards)
                for d in range(shards)]
    raise ValueError(kind)


FILLS = ["empty", "one_block_full", "first_block_full", "sum_is_l_in",
         "every_block_part_full"]


@pytest.mark.parametrize("kind", FILLS)
@pytest.mark.parametrize("w1", [1, 2, 3])
@pytest.mark.parametrize("shards", [2, 4, 8])
def test_unpack_is_the_compaction(shards, w1, kind):
    bucket = default_bucket(shards)
    fill = fills(kind, shards, bucket, L_IN)
    assert sum(fill) <= L_IN and max(fill) <= bucket
    tgt, sender, words = received(fill, bucket, w1)
    got = unpack(tgt, sender, words, fill, bucket, L_IN)
    want = reference_unpack(tgt, sender, words, L_IN)
    for g, w, part in zip(got, want, ("tgt", "sender", "words")):
        np.testing.assert_array_equal(g, w, err_msg=part)


@pytest.mark.parametrize("bucket", [13, 47, 48, 49, 200])
def test_unpack_at_any_explicit_bucket(bucket):
    """`route_bucket` set by hand: shorter than the short list (the
    last block's pad does not reach its end), longer than it (a block
    is cut by the final slice), and either side of equal."""
    shards, w1 = 4, 2
    rng = np.random.default_rng(bucket)
    for _ in range(6):
        fill = rng.integers(0, min(bucket, L_IN // shards) + 1, shards)
        tgt, sender, words = received(fill, bucket, w1)
        got = unpack(tgt, sender, words, fill, bucket, L_IN)
        want = reference_unpack(tgt, sender, words, L_IN)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("w1", [1, 2, 3])
@pytest.mark.parametrize("shards", [2, 4, 8])
def test_pack_then_unpack_is_the_outbox_by_destination(shards, w1):
    """One shard's outbox through `_route_pack` and its own buckets
    back through `_route_unpack`: the valid entries, grouped by
    destination shard, each group in arrival order (FIFO), the invalid
    gone."""
    n_local, e = 16, L_IN
    bucket = default_bucket(shards, e)
    rng = np.random.default_rng(shards * 10 + w1)
    tgt = rng.integers(0, shards * n_local, e).astype(np.int32)
    tgt[rng.random(e) < 0.3] = -1
    sender = np.arange(e, dtype=np.int32)          # arrival order
    words = np.stack([sender + 1000 * (i + 1) for i in range(w1)])
    _sorted, (_start, cnt, acc), (bt, bs, bw, _fill) = jax.jit(
        functools.partial(route._route_pack, shards=shards,
                          n_local=n_local, bucket=bucket))(
        jnp.asarray(tgt), jnp.asarray(sender), jnp.asarray(words))
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(cnt))
    got = unpack(bt, bs, bw, np.asarray(acc), bucket, e)
    order = np.argsort(np.where(tgt >= 0, tgt // n_local, shards),
                       kind="stable")[:int((tgt >= 0).sum())]
    pad = e - order.shape[0]
    np.testing.assert_array_equal(
        got[0], np.concatenate([tgt[order], np.full(pad, -1)]))
    np.testing.assert_array_equal(
        got[1], np.concatenate([sender[order], np.full(pad, -1)]))
    np.testing.assert_array_equal(
        got[2], np.concatenate([words[:, order],
                                np.zeros((w1, pad), np.int32)], axis=1))


def test_the_static_guard():
    """The short list exists only on a mesh whose received buckets are
    longer than it: one chip and a small explicit `route_bucket` keep
    the window they had."""
    assert route._unpack_fits(4, 48, 48)
    assert route._unpack_fits(2, 25, 48)
    assert not route._unpack_fits(1, 0, 48)
    assert not route._unpack_fits(4, 12, 48)      # 4 x 12 == l_in
    assert not route._unpack_fits(4, 8, 48)


# The unpack alone, compiled for a described v5e (no chip: libtpu's
# compiler, in a child: tests/_hlo.py). What the chip would run under
# it: reads by index, writes by index, sorts, and how many arrays as long
# as the padded list (`shards * bucket`) it writes — the joined buffer is
# `l_in + bucket`, a copy of what was received would be the parent's
# padding over again.
FOR_THE_CHIP = """
from ponyc_tpu.runtime import route
from ponyc_tpu.runtime.delivery import Entries
shards, bucket, w1 = 4, {bucket}, {w1}
fn = functools.partial(route._route_unpack, shards=shards, bucket=bucket,
                       l_in=bucket)
args = (Entries(arg(shards * bucket), arg(shards * bucket),
                arg(w1, shards * bucket)), arg(shards))
"""


@pytest.mark.parametrize("w1", [2])
def test_for_the_chip_the_unpack_is_contiguous_copies(w1):
    """What the chip runs for the unpack reads nothing by index, writes
    nothing by index, sorts nothing, and writes no array as long as the
    padded list. At the mesh cell's own size (a bucket of 8,392,704): a
    list small enough for the chip's fast memory is prefetched there
    whole, which is a copy of it, if a cheap one."""
    bucket = 8392704
    seen = _hlo.v5e_counts(FOR_THE_CHIP.format(bucket=bucket, w1=w1),
                           length=4 * bucket)
    assert seen == {"gathers": 0, "scatters": 0, "sorts": 0, "long": 0}
