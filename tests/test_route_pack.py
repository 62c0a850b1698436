"""`route._route_pack` — a shard's entries into its all-to-all buckets by
one payload-carrying sort and a contiguous slice a destination — against
the plain NumPy form of what it replaced: a stable argsort by
destination shard, every array read back through the permutation, and a
dense `[shards, bucket]` gather that pads each destination's block.
Every output must be that form's bit for bit, for narrow messages and
wide ones, and what overflows a bucket must reach `_route_spill` as it
did.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _hlo
from ponyc_tpu.runtime import route
from ponyc_tpu.runtime.delivery import prefix_len

SHARDS, N_LOCAL = 4, 16


def reference_pack(tgt, sender, words, shards, n_local, bucket):
    """The gather form, in NumPy."""
    e = tgt.shape[0]
    dest = np.where(tgt >= 0, tgt // n_local, shards).astype(np.int32)
    perm = np.argsort(dest, kind="stable")
    dt, ts, ss, ws = dest[perm], tgt[perm], sender[perm], words[:, perm]
    bounds = np.searchsorted(dt, np.arange(shards + 1), side="left")
    seg_start = bounds[:-1].astype(np.int32)
    cnt = (bounds[1:] - seg_start).astype(np.int32)
    acc = np.minimum(cnt, bucket).astype(np.int32)
    j = np.arange(bucket)[None, :]
    fill = j < acc[:, None]
    src = np.minimum(seg_start[:, None] + j, e - 1)
    bt = np.where(fill, ts[src], -1).reshape(-1)
    bs = np.where(fill, ss[src], -1).reshape(-1)
    fill_f = fill.reshape(-1)
    bw = np.where(fill_f[None, :], ws[:, src.reshape(-1)], 0)
    return ((dt, ts, ss, ws), (seg_start, cnt, acc), (bt, bs, bw, fill_f))


def reference_spill(packed, shards, bucket, rspill_cap):
    """What does not fit its bucket, in sorted (= arrival) order."""
    (dt, ts, ss, ws), (seg_start, _cnt, _acc), _ = packed
    rank = np.arange(dt.shape[0]) - seg_start[np.minimum(dt, shards - 1)]
    rej = np.flatnonzero((dt < shards) & (rank >= bucket))
    kept = rej[:rspill_cap]
    pad = rspill_cap - kept.shape[0]
    return (np.concatenate([ts[kept], np.full(pad, -1)]),
            np.concatenate([ss[kept], np.full(pad, -1)]),
            np.concatenate([ws[:, kept], np.zeros((ws.shape[0], pad))],
                           axis=1),
            min(rej.shape[0], rspill_cap), rej.shape[0] > rspill_cap,
            ss[rej])


def _entries(e, w1, seed, dests=None, invalid=0.25):
    """`e` entries: targets over `dests` (default every shard), a share
    of them invalid, senders and words that tell every entry apart."""
    rng = np.random.default_rng(seed)
    dests = list(range(SHARDS)) if dests is None else dests
    tgt = (rng.choice(dests, e) * N_LOCAL
           + rng.integers(0, N_LOCAL, e)).astype(np.int32)
    tgt = np.where(rng.random(e) < invalid, -1, tgt).astype(np.int32)
    sender = rng.integers(0, SHARDS * N_LOCAL, e).astype(np.int32)
    words = (np.arange(e, dtype=np.int32)[None, :] * 8
             + np.arange(w1, dtype=np.int32)[:, None] + 1)
    return tgt, sender, words


def _check(tgt, sender, words, bucket):
    """The pack's every output is the reference's."""
    got = jax.jit(functools.partial(
        route._route_pack, shards=SHARDS, n_local=N_LOCAL, bucket=bucket))(
        jnp.asarray(tgt), jnp.asarray(sender), jnp.asarray(words))
    want = reference_pack(tgt, sender, words, SHARDS, N_LOCAL, bucket)
    for g_part, w_part, part in zip(got, want,
                                    ("sorted", "segments", "buckets")):
        for i, (g, w) in enumerate(zip(g_part, w_part)):
            np.testing.assert_array_equal(np.asarray(g), w,
                                          err_msg=f"{part}[{i}]")
    return got, want


CASES = {
    # the mesh cell's geometry: a bucket as long as the entries, so an
    # unpadded dynamic_slice would clamp every start to 0
    "bucket_is_e": dict(e=96, bucket=96),
    "bucket_above_e": dict(e=40, bucket=64),
    "bucket_one": dict(e=48, bucket=1),
    # shards 1 and 2 get nothing: two empty segments in the middle
    "empty_destinations": dict(e=64, bucket=24, dests=[0, 3]),
    # the last shard gets nothing and nothing is invalid: its segment
    # starts at e, the slice lies wholly in the pad
    "empty_last_no_tail": dict(e=64, bucket=32, dests=[0, 1, 2],
                               invalid=0.0),
    "all_invalid": dict(e=64, bucket=16, invalid=2.0),
    "none_invalid": dict(e=64, bucket=64, invalid=0.0),
    "one_destination": dict(e=80, bucket=80, dests=[2], invalid=0.0),
}


WIDTHS = [1, 2, 5, 9]     # message words + 1: one-word to trace lanes on


@pytest.mark.parametrize("w1", WIDTHS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_pack_is_the_gather_forms(case, w1):
    spec = dict(CASES[case])
    e, bucket = spec.pop("e"), spec.pop("bucket")
    tgt, sender, words = _entries(e, w1, seed=len(case) + w1, **spec)
    _check(tgt, sender, words, bucket)


@pytest.mark.parametrize("w1", WIDTHS)
def test_overflow_reaches_the_route_spill_unchanged(w1):
    """A bucket below the fullest destination: `acc` < `cnt`, the
    bucket holds each destination's first `bucket` entries and
    `_route_spill` parks the rest, in order."""
    e, bucket, cap = 128, 8, 64
    tgt, sender, words = _entries(e, w1, seed=11 + w1, invalid=0.1)
    got, want = _check(tgt, sender, words, bucket)
    (dt, ts, ss, ws), (seg_start, cnt, acc), _ = got
    assert int(np.max(np.asarray(cnt) - np.asarray(acc))) > 0
    n = SHARDS * N_LOCAL
    (spill, count, over, muted, _refs, _ovf, _remote,
     _prefix) = jax.jit(functools.partial(
        route._route_spill, shards=SHARDS, n_local=N_LOCAL, bucket=bucket,
        rspill_cap=cap, overload_occ=48, shard_base=jnp.int32(0),
        mute_slots=4))(
        ts, ss, ws, dt, seg_start, cnt - acc, seg_start[-1] + cnt[-1],
        head=jnp.zeros((N_LOCAL,), jnp.int32),
        tail=jnp.zeros((N_LOCAL,), jnp.int32),
        hot_anywhere=jnp.bool_(False),
        hot_global=jnp.zeros((n,), jnp.int8),
        pressured_local=jnp.zeros((N_LOCAL,), jnp.bool_))
    w_tgt, w_sender, w_words, w_count, w_over, rejected = reference_spill(
        want, SHARDS, bucket, cap)
    np.testing.assert_array_equal(np.asarray(spill.tgt), w_tgt)
    np.testing.assert_array_equal(np.asarray(spill.sender), w_sender)
    np.testing.assert_array_equal(np.asarray(spill.words), w_words)
    assert (int(count), bool(over)) == (w_count, w_over)
    # every rejected entry's sender on this shard mutes
    local = rejected[(rejected >= 0) & (rejected < N_LOCAL)]
    assert set(np.flatnonzero(np.asarray(muted))) == set(local.tolist())


@pytest.mark.parametrize("w1", [2, 6])
@pytest.mark.parametrize("bucket", [4, 12, 64])
def test_equal_destinations_keep_spill_then_outbox_order(bucket, w1):
    """FIFO: the entries are `[route spill, outbox]`; within a
    destination a bucket's slots (and then the spill) hold them in that
    order, a retried entry before this tick's."""
    e = 24 + 40                                    # spill, then outbox
    rng = np.random.default_rng(bucket)
    tgt = (rng.integers(0, SHARDS, e) * N_LOCAL + 3).astype(np.int32)
    tgt[rng.random(e) < 0.2] = -1
    sender = np.arange(e, dtype=np.int32)          # arrival order
    words = np.stack([sender + 1000 * (i + 1) for i in range(w1)])
    got, _ = _check(tgt, sender, words, bucket)
    bt, bs, bw, fill_f = (np.asarray(x) for x in got[2])
    for d in range(SHARDS):
        block = slice(d * bucket, (d + 1) * bucket)
        filled = fill_f[block]
        assert filled[:filled.sum()].all()               # a prefix
        order = bs[block][filled]
        arrived = np.flatnonzero(tgt // N_LOCAL == d)
        np.testing.assert_array_equal(order, arrived[:bucket])
        for i in range(w1):
            np.testing.assert_array_equal(bw[i, block][filled],
                                          order + 1000 * (i + 1))
        assert (bt[block][filled] // N_LOCAL == d).all()


@pytest.mark.parametrize("seed", range(8))
def test_random_geometries(seed):
    rng = np.random.default_rng(100 + seed)
    e = int(rng.integers(8, 200))
    bucket = int(rng.integers(1, 2 * e))
    w1 = int(rng.integers(1, 12))
    tgt, sender, words = _entries(e, w1, seed, invalid=float(rng.random()))
    _check(tgt, sender, words, bucket)


# The pack alone, compiled for a described v5e (no chip: libtpu's
# compiler, in a child: tests/_hlo.py). What the chip would run: how
# many arrays as long as the padded entries it writes (the pad must fold
# into each destination's slice) and its gathers, the bounds' binary
# search aside.
FOR_THE_CHIP = """
from ponyc_tpu.runtime import route
e, w1 = {e}, {w1}
fn = functools.partial(route._route_pack, shards=4, n_local=e // 8, bucket=e)
args = (arg(e), arg(e), arg(w1, e))
"""


@pytest.mark.parametrize("w1", [2, 9])
def test_for_the_chip_the_pad_folds_into_the_slices(w1):
    """`blocks()` pads the sorted entries by a bucket so that no slice
    clamps. The chip's compiler must fold that pad into each
    destination's slice: a padded copy written on every tick would be
    the parent's padding bytes over again. And it reads nothing by
    index."""
    e = 4096
    seen = _hlo.v5e_counts(FOR_THE_CHIP.format(e=e, w1=w1), length=2 * e)
    assert (seen["long"], seen["gathers"]) == (0, 0)


# `_route_spill` alone, compiled for the described v5e the same way: what
# a quiet tick pays for it. The lookup of the sorted entries' targets in
# the mesh-wide hot word sits behind world bits 0 and 3
# (`hot_anywhere`), the overflow and the mutes behind the
# conditional they always had; both at the entries' length and at a
# quarter of it (PR 50), behind the conditional that asks whether the
# valid entries fit the quarter.
SPILL_FOR_THE_CHIP = """
import re
sys.path.insert(0, {tests!r})
import _hlo
from ponyc_tpu.runtime import route
from ponyc_tpu.runtime.state import phase_scope
e, shards, n = {e}, 4, {n}
flag = lambda *shape, dtype=jnp.bool_: jax.ShapeDtypeStruct(
    shape, dtype, sharding=SingleDeviceSharding(device))
def fn(ts, ss, ws, dt, seg_start, over, n_live, head, tail, anywhere,
       everyone, mine):
    with phase_scope("route/spill"):
        return route._route_spill(
            ts, ss, ws, dt, seg_start, over, n_live, shards=shards, n_local=n,
            bucket=e, rspill_cap=4096, overload_occ=48, head=head, tail=tail,
            shard_base=jnp.int32(0), mute_slots=4,
            hot_anywhere=anywhere, hot_global=everyone,
            pressured_local=mine)
args = (arg(e), arg(e), arg(2, e), arg(e), arg(shards), arg(shards), arg(),
        arg(n), arg(n), flag(), flag(shards * n, dtype=jnp.int8), flag(n))
def report(text):
    entry = text[text.index("\\nENTRY "):].split("\\n}}")[0].splitlines()[2:]
    wide = []
    for line in entry:
        typed, opcode = re.match(
            r"\\s*(?:ROOT )?%?[\\w.\\-]+ = (.*?) ([\\w\\-]+)\\(", line).groups()
        if re.search(r"[\\[,]%d[\\],]" % e, typed):
            wide.append(opcode)
    return dict(conds=_hlo.branch_ops(text, "pony/route/spill/cond"),
                gathers=text.count(" gather("), wide=sorted(set(wide)))
"""


def test_for_the_chip_a_quiet_ticks_spill_holds_no_gather():
    """Outside its conditionals the spill reads nothing by index and
    writes nothing as long as the entries (the branch's zeros come out
    of the conditional; the compiler may prefetch a branch's operand,
    which is a copy). The first conditional chooses a length, the
    entries' or a quarter of them; at either, the lookup's branch holds
    the one gather of that many flags, where nothing overflowed and
    nothing is pressured the second conditional reaches nothing, and
    nothing at the quarter reads or writes at the entries' length."""
    import os
    e = 1 << 16
    short = prefix_len(e)
    seen = _hlo.v5e_counts(SPILL_FOR_THE_CHIP.format(
        tests=os.path.dirname(os.path.abspath(__file__)), e=e, n=e // 8))
    conds = seen["conds"]
    assert len(conds) == 5
    outer = max(conds, key=lambda branches: sum(map(len, branches)))
    inner = [c for c in conds if c is not outer]
    in_branches = 0
    for length, reached in zip((e, short), outer):   # false branch first
        lookup = [["gather", [length]]]
        (quiet_zeros, looked_up), = [c for c in inner if c[1] == lookup]
        (quiet, pressure), = [c for c in inner if c[1] != lookup
                              and lookup[0] in c[1]]
        assert (quiet_zeros, looked_up, quiet) == ([], lookup, [])
        assert sorted(looked_up + pressure) == reached
        in_branches += sum(op == "gather" for op, _dims in reached)
    assert not [op for op in outer[1] if e in op[1]]
    assert seen["gathers"] == in_branches > 2
    computes_nothing = {"parameter", "tuple", "get-tuple-element", "bitcast",
                        "conditional", "copy-start", "copy-done"}
    assert set(seen["wide"]) <= computes_nothing, seen["wide"]
