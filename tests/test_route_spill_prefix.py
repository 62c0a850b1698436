"""`route._route_spill` at its two lengths (PR 50).

The route's sort keys the invalid tail `shards`, so the valid entries
are a prefix of the sorted ones and the spill — the hot-word lookup, the
overflow's compaction, the senders' exemption, the ref table's scatters
— asks nothing about the rest. The window holds it at the entries'
length `e` and at `prefix_len(e)` (a quarter), and the tick's own
count of valid entries chooses. Held here:

- the function itself: the same seven results bit for bit at either
  length, for a hot receiver, a link that overflows, both and neither,
  with `L - 1`, `L` and `L + 1` valid entries (the boundary);
- two-shard worlds under declared pressure and under a full link,
  ticked with the seam patched to each length: the same state leaf for
  leaf after every tick, but the counter of the choice;
- `n_route_prefix` counts exactly the shard-ticks that read the prefix
  alone, is 0 on a quiet mesh, and no one-chip window holds any of it.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _hlo
from ponyc_tpu import RuntimeOptions
from ponyc_tpu.runtime import engine, route
from ponyc_tpu.runtime.delivery import prefix_len
from ponyc_tpu.runtime.state import ROUTE_COUNTERS, layout_sizes
from test_mesh_pressure import Burst, _leaves, _run_pressure

SHARDS, N_LOCAL, E, RSPILL = 4, 64, 1024, 512
SHORT = prefix_len(E)
RESULTS = ("route spill", "spill count", "spill overflow", "newly muted",
           "mute refs", "ref overflow", "remote mutes")


def _sorted_entries(n_live, seed):
    """`E` entries of which `n_live` are valid, targets over the whole
    mesh and every sender on shard 1, packed as `_route` packs them."""
    rng = np.random.default_rng(seed)
    tgt = np.full(E, -1, np.int32)
    tgt[rng.permutation(E)[:n_live]] = rng.integers(
        0, SHARDS * N_LOCAL, n_live)
    sender = (N_LOCAL + rng.integers(0, N_LOCAL, E)).astype(np.int32)
    words = (np.arange(E, dtype=np.int32)[None, :] * 4
             + np.arange(2, dtype=np.int32)[:, None] + 1)
    return tgt, sender, words


def _spill(n_live, hot, overflow, prefix, seed=5):
    """`_route_spill` over `n_live` valid entries with the route's
    `prefix_len` = `prefix`; `hot`: a third of the mesh's rows
    overloaded, two of the senders themselves; `overflow`: a bucket of
    48 for ~64 entries a destination."""
    tgt, sender, words = _sorted_entries(n_live, seed)
    bucket = 48 if overflow else E
    rng = np.random.default_rng(seed + 1)
    hot_global = ((rng.random(SHARDS * N_LOCAL) < 0.33) & hot).astype(np.int8)
    head = np.zeros(N_LOCAL, np.int32)
    tail = np.where(np.arange(N_LOCAL) < 2, 60, 0).astype(np.int32)

    def fn(tgt, sender, words, hot_global, head, tail):
        ((dt, ts, ss, ws), (seg_start, cnt, acc), _) = route._route_pack(
            tgt, sender, words, shards=SHARDS, n_local=N_LOCAL,
            bucket=bucket)
        return route._route_spill(
            ts, ss, ws, dt, seg_start, cnt - acc, seg_start[-1] + cnt[-1],
            shards=SHARDS, n_local=N_LOCAL, bucket=bucket,
            rspill_cap=RSPILL, overload_occ=48, head=head, tail=tail,
            shard_base=jnp.int32(N_LOCAL), mute_slots=4,
            hot_anywhere=jnp.bool_(hot), hot_global=hot_global,
            pressured_local=jnp.zeros(N_LOCAL, jnp.bool_))

    with pytest.MonkeyPatch.context() as patched:
        patched.setattr(route, "prefix_len", prefix)
        out = jax.jit(fn)(tgt, sender, words, hot_global, head, tail)
    return jax.tree.map(np.asarray, out)


CASES = {"hot": (True, False), "overflow": (False, True),
         "both": (True, True), "nothing": (False, False)}


@pytest.mark.parametrize("n_live", ["L-1", "L", "L+1"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_same_entries_at_two_lengths(case, n_live):
    """A quarter of 1,024 sorted entries is 256: 255 and 256 valid
    entries run the prefix, 257 the whole, each against the function
    that has the whole length alone (the parent's)."""
    assert SHORT == 256 < RSPILL        # the cell's order: cap > prefix
    n_live = SHORT + {"L-1": -1, "L": 0, "L+1": 1}[n_live]
    hot, overflow = CASES[case]
    *here, took_prefix = _spill(n_live, hot, overflow, prefix_len)
    *there, took_whole = _spill(n_live, hot, overflow, lambda e: e)
    assert took_whole == 0
    assert took_prefix == (n_live <= SHORT and (hot or overflow))
    for name, got, want in zip(RESULTS, here, there, strict=True):
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                        strict=True):
            np.testing.assert_array_equal(g, w, err_msg=name)
    spill, count, _over, muted, refs, _ovf, remote = here
    # the case did what it says
    assert (count > 0) == overflow == bool((spill.tgt >= 0).any())
    assert count == (spill.tgt >= 0).sum()
    assert muted.any() == (hot or overflow)
    assert not muted[:2].any()          # the overloaded senders: exempt
    assert (remote > 0) == (hot or overflow)
    assert ((refs >= 0).any(axis=0) == muted).all()


# ---------------------------------------------------------- the worlds

QUIET, PRESSURED, AFTER = 2, 4, 10
# a prefix some engaged shard-ticks of the world fit and others do not
TINY = {"declared": 4, "link": 8}


@functools.lru_cache(maxsize=None)
def _ticked(kind, prefix):
    """A two-shard world ticked with the route's `prefix_len` = `prefix`
    ("quarter": the program's own; "whole": the one length; "tiny":
    TINY[kind] entries).
    `declared`: four senders on the shard that is not the sink's, the sink
    declares pressure for PRESSURED ticks, they mute at routing, release.
    `link`: sixteen senders flood the sink through a bucket of 4, so the
    links overflow, entries park and their senders mute. Every state leaf
    after every tick."""
    opts = RuntimeOptions(mailbox_cap=64, batch=4, max_sends=2, msg_words=2,
                          mesh_shards=2, spill_cap=512, inject_slots=64,
                          quiesce_interval=1,
                          **({"route_bucket": 4} if kind == "link" else {}))
    seam = {"quarter": prefix_len, "whole": lambda e: e,
            "tiny": lambda e: TINY[kind]}[prefix]
    with pytest.MonkeyPatch.context() as patched:
        patched.setattr(route, "prefix_len", seam)
        rt, sink, srcs = _run_pressure(opts, 16, 12, go=False)
        sink, n_local = int(sink), rt.program.n_local
        seen = []

        def tick(inject=None):
            rt.state, _aux = rt._step(
                rt.state, *(inject or rt._empty_inject))
            seen.append(_leaves(rt, but=()))

        remote = [s for s in srcs if int(s) // n_local != sink // n_local]
        for s in srcs if kind == "link" else remote[:4]:
            rt.send(int(s), Burst.go, 0)
        tick(rt._drain_inject())
        for _ in range(QUIET - 1):
            tick()
        if kind == "declared":
            rt.apply_backpressure([sink])
        for _ in range(PRESSURED):
            tick()
        if kind == "declared":
            rt.release_backpressure([sink])
        for _ in range(AFTER):
            tick()
        counters = {c: rt.counter(c) for c in ROUTE_COUNTERS}
        # the sorted entries: a shard's route spill and its outboxes
        counters["short"] = seam(
            opts.spill_cap + layout_sizes(rt.program, rt.opts)[0])
        rt.stop()
    return seen, counters


def _counted(seen, name):
    """[ticks, shards]: what each tick added to a route counter."""
    total = np.stack([leaves[f".route_counts['{name}']"] for leaves in seen])
    return np.diff(total, axis=0, prepend=0)


@pytest.mark.parametrize("prefix", ["whole", "quarter", "tiny"])
@pytest.mark.parametrize("kind", ["declared", "link"])
def test_the_script_does_what_it_says(kind, prefix):
    """Senders muted at routing, behind a receiver of the other shard,
    and under `link` entries parked. (One world a test: the tests below
    read these runs.)"""
    seen, counters = _ticked(kind, prefix)
    assert len(seen) == QUIET + PRESSURED + AFTER
    assert counters["n_remote_mutes"] > 0
    assert (max(leaves[".rspill_count"].max() for leaves in seen) > 0) \
        == (kind == "link")
    assert (counters["n_route_prefix"] > 0) == (prefix != "whole")


@pytest.mark.parametrize("prefix", ["quarter", "tiny"])
@pytest.mark.parametrize("kind", ["declared", "link"])
def test_a_world_ends_each_tick_in_the_same_state_at_either_length(
        kind, prefix):
    seen, _ = _ticked(kind, prefix)
    want, _ = _ticked(kind, "whole")
    mine = ".route_counts['n_route_prefix']"
    for t, (got, ref) in enumerate(zip(seen, want, strict=True)):
        assert got.keys() == ref.keys() and mine in got
        bad = [k for k in got
               if k != mine and not np.array_equal(got[k], ref[k])]
        assert not bad, (t, bad[:6])


@pytest.mark.parametrize("prefix", ["quarter", "tiny"])
@pytest.mark.parametrize("kind", ["declared", "link"])
def test_n_route_prefix_counts_exactly_the_prefix_shard_ticks(kind, prefix):
    """A shard-tick counts where its valid entries fitted the prefix AND
    it looked up or parked. Both are read off the other counters: a
    tick's valid entries are what it shipped (`n_routed`) and what it
    parked (the route spill's growth; a parked entry is valid again on
    the retry), it looked up where `n_route_pressure` moved, and a link
    overflowed where its spill is not empty after the tick."""
    seen, counters = _ticked(kind, prefix)
    short = counters["short"]
    parked = np.stack([leaves[".rspill_count"] for leaves in seen])
    n_live = _counted(seen, "n_routed") + parked
    engaged = (_counted(seen, "n_route_pressure") > 0) | (parked > 0)
    want = (n_live <= short) & engaged
    np.testing.assert_array_equal(_counted(seen, "n_route_prefix"), want)
    assert counters["n_route_prefix"] == want.sum() > 0
    if prefix == "tiny":    # some shard-tick did not fit, and ran whole
        assert (engaged & ~want).any()
    elif kind == "declared":    # a world that always fits, no full link
        assert counters["n_route_prefix"] == counters["n_route_pressure"]


def test_a_quiet_mesh_reads_neither_length():
    """`tests/test_mesh_ubench.py`'s world: nobody overloaded, no link
    full — the spill's quiet side on every tick, which is no prefix
    tick whether the entries would fit or not."""
    from test_mesh_ubench import _world
    world = _world(4, "random", actors=256)
    for _tick in range(6):
        assert world.rt.run(max_steps=1) == 0
    assert world.rt.counter("n_routed") > 0
    assert world.rt.counter("n_route_prefix") == 0
    assert world.rt.counter("n_route_pressure") == 0
    world.rt.stop()


ONE_CHIP = ("ubench", "ring", "fanin", "gups", "spreader", "bank")


@pytest.mark.parametrize("window", ONE_CHIP)
def test_a_one_chip_window_holds_nothing_of_the_route(window, monkeypatch):
    """The one-chip cells' windows are the parent's text for text (`python
    tests/_hlo.py DIR` on both trees, `diff -r`: PERF.md §6, PR 50), and
    cannot be anything else: tracing one never enters `_route` or
    `_route_spill`, its state holds no route leaf, its text no operation
    under `pony/route/spill`."""
    def never(*_args, **_kw):
        raise AssertionError("the route, on one chip")
    monkeypatch.setattr(route, "_route", never)
    monkeypatch.setattr(route, "_route_spill", never)
    rt = _hlo.WINDOWS[window]()
    assert set(rt.state.route_counts) <= {"n_prefix"}
    assert rt.counter("n_route_prefix") == 0
    text = engine.jit_multi_step_gated(rt.program, rt.opts, rt.mesh).lower(
        rt.state, *rt._empty_inject, jnp.int32(4), jnp.bool_(True),
        rt._zero_aux).as_text(debug_info=True)
    rt.stop()
    assert "pony/delivery" in text and "pony/route/spill" not in text
