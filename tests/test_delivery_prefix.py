"""After the plan's sort the live entries are a prefix (delivery.py,
`deliver`): the body of delivery is held at two static lengths, the
whole list and `prefix_len` of it, and the tick's own count of live
entries chooses. The branch is a matter of cost alone: the SAME live
entries in the same order, laid into a list long enough that they fit
its prefix and into one short enough that they do not, must leave the
same mailboxes, tails, spill, mutes, counters and bounds, bit for bit;
FIFO must hold through ticks that alternate between the two; and
`n_prefix` counts exactly the ticks that took the prefix."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ponyc_tpu import RuntimeOptions, flight
from ponyc_tpu.runtime import delivery
from ponyc_tpu.runtime.delivery import Entries, deliver, prefix_len

N = 72
ONE = [("Actor", 0, N, 3)]                 # (type, s0, s1, 1+W)
# savina-bank's shape at 8 banks: a narrow deep cohort beside a wide
# shallow one, each with a ring of its own depth.
BANK = [("Teller", 0, 8, 2), ("Account", 8, N, 3)]
CAPS = {"Actor": 16, "Teller": 32, "Account": 16}
SPILL = 512                                # above a short list's prefix
# What of a DeliveryResult carries the list's length (the stored plan)
# or says which branch ran.
OF_THE_LIST = ("plan_key", "plan_perm", "n_prefix")


def _world(kind, v, seed=0):
    """The tables and `v` live entries in arrival order. `kind`:
    'plain'; 'pressure' (half the rings nearly full, a low overload line, some
    rows with declared pressure: the tick rejects, spills and mutes);
    'levels' (three priorities contending for the last slots); 'dead'
    (a third of the rows are dead: their segments are dead letters);
    'traced' (trace side lanes on); 'bank' / 'bank-pressure' (two
    cohorts of different capacity and width)."""
    rng = np.random.default_rng(seed)
    layout = BANK if kind.startswith("bank") else ONE
    tracing = kind == "traced"
    cap = np.concatenate([np.full(s1 - s0, CAPS[name])
                          for name, s0, s1, _w in layout])
    tight = kind in ("pressure", "levels", "bank-pressure")
    occ = (rng.integers(cap - 3, cap + 1) if tight
           else rng.integers(0, cap // 2))
    # the odd rows stay calm: a sender over its own overload line is
    # exempt from muting, and the senders are drawn from them
    calm = np.arange(N) % 2 == 1
    occ[calm] = np.minimum(occ[calm], 2)
    head = rng.integers(10 * cap, 1000 * cap)
    alive = np.ones(N, bool)
    if kind == "dead":
        alive[::3] = False
        occ[~alive] = 0
    w1 = max(w for *_l, w in layout) + (2 if tracing else 0)
    n_levels = 3 if kind == "levels" else 1
    return dict(
        layout=layout, tracing=tracing, n_levels=n_levels,
        cap=cap, overload=cap * 3 // 4 if tight else cap,
        pressured=((rng.random(N) < 0.3) & ~calm if "pressure" in kind
                   else None),
        head=head, tail=head + occ, alive=alive,
        buf={name: rng.integers(-99, -1, (CAPS[name], w, s1 - s0))
             for name, s0, s1, w in layout},
        tbuf={name: rng.integers(-99, -1, (CAPS[name], 2, s1 - s0))
              for name, s0, s1, _w in layout},
        tgt=rng.integers(0, N, v), sender=rng.choice(np.flatnonzero(calm), v),
        words=rng.integers(1, 1 << 30, (w1, v)),
        level=rng.integers(0, n_levels, v))


def _lay(world, e, seed):
    """The live entries, in order, at random places of a list of `e`;
    the holes are empty slots (-1) and targets out of range, with words
    and levels that must not matter."""
    rng = np.random.default_rng(seed)
    v = world["tgt"].shape[0]
    at = np.sort(rng.choice(e, v, replace=False))
    tgt = rng.choice([-1, -1, N, N + 3], e)
    sender = rng.integers(-1, N, e)
    words = rng.integers(1, 1 << 30, (world["words"].shape[0], e))
    level = rng.integers(0, world["n_levels"], e)
    tgt[at], sender[at], level[at] = (world["tgt"], world["sender"],
                                      world["level"])
    words[:, at] = world["words"]
    return tgt, sender, words, level, at


def _uniform(x):
    """An int where every row has the same, as state.rows_of gives it."""
    return int(x[0]) if (x == x[0]).all() else jnp.asarray(x, jnp.int32)


@functools.cache
def _delivery(kind):
    """`deliver` jitted for one kind of world."""
    w = _world(kind, 0)

    def fn(buf, tbuf, head, tail, alive, pressured, tgt, sender, words,
           level, *plan):
        return deliver(
            buf, head, tail, alive, Entries(tgt, sender, words),
            n_local=N, mailbox_cap=_uniform(w["cap"]), spill_cap=SPILL,
            overload_occ=_uniform(w["overload"]), shard_base=jnp.int32(0),
            cohort_layout=w["layout"],
            level=level if w["n_levels"] > 1 else None,
            n_levels=w["n_levels"], plan=plan or None,
            pressured=pressured if w["pressured"] is not None else None,
            trace_buf=tbuf if w["tracing"] else None)
    return jax.jit(fn)


def _deliver(kind, world, e, seed, plan=()):
    tgt, sender, words, level, at = _lay(world, e, seed)
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    pressured = world["pressured"]
    res = _delivery(kind)(
        jax.tree.map(i32, world["buf"]), jax.tree.map(i32, world["tbuf"]),
        i32(world["head"]), i32(world["tail"]), jnp.asarray(world["alive"]),
        jnp.zeros(N, bool) if pressured is None else jnp.asarray(pressured),
        i32(tgt), i32(sender), i32(words), i32(level), *plan)
    return jax.device_get(res), at


def _same(a, b):
    """Leaf for leaf equal on everything that is not of the list."""
    for name in a._fields:
        if name not in OF_THE_LIST:
            for x, y in zip(jax.tree.leaves(getattr(a, name)),
                            jax.tree.leaves(getattr(b, name)), strict=True):
                np.testing.assert_array_equal(x, y, err_msg=name)


def _live_order(res, at):
    """The front of the permutation as positions among the LIVE entries:
    what the two lists' plans must agree on."""
    rank = np.full(res.plan_perm.shape[0], -1)
    rank[at] = np.arange(at.shape[0])
    return rank[res.plan_perm[:at.shape[0]]]


def _list_that_fits(v):
    """A list whose prefix holds `v` live entries."""
    return 512 * max(2, -(-v // 128))


def _list_that_does_not(v):
    """A list that holds `v` live entries and whose prefix does not."""
    e = -(-(v + 8) // 128) * 128
    assert prefix_len(e) < v <= e, "too few live entries for two lengths"
    return e


KINDS = ["plain", "pressure", "levels", "dead", "traced", "bank",
         "bank-pressure"]


@pytest.mark.parametrize("kind", KINDS)
def test_the_same_entries_in_two_lists(kind):
    world = _world(kind, 200, seed=KINDS.index(kind))
    over_prefix, at_p = _deliver(kind, world, _list_that_fits(200), 1)
    over_list, at_l = _deliver(kind, world, _list_that_does_not(200), 2)
    assert (over_prefix.n_prefix, over_list.n_prefix) == (1, 0)
    _same(over_prefix, over_list)
    np.testing.assert_array_equal(_live_order(over_prefix, at_p),
                                  _live_order(over_list, at_l))
    # the kinds do what they are named for
    assert over_list.n_delivered > 0
    if kind in ("pressure", "levels", "bank-pressure"):
        assert over_list.n_rejected > 0 and over_list.spill_count > 0
        assert (over_list.spill.tgt >= 0).sum() == over_list.spill_count
    if "pressure" in kind:
        assert over_list.newly_muted.any()
    if kind == "dead":
        assert over_list.n_deadletter > 0


@pytest.mark.parametrize("v", ["0", "L", "L+1"])
def test_at_the_boundary(v):
    """In a list of 2,048 (a prefix of 512): no live entry delivers
    nothing and counts no prefix tick; 512 take the prefix; 513 the
    list. Each against the same entries in a list that takes the other
    branch."""
    e = 2048
    short = prefix_len(e)
    assert short == 512
    v, other, want = {"0": (0, 640, (0, 0)),
                      "L": (short, _list_that_does_not(short), (1, 0)),
                      "L+1": (short + 1, _list_that_fits(short + 1),
                              (0, 1))}[v]
    world = _world("plain", v, seed=3)
    here, _ = _deliver("plain", world, e, 1)
    there, _ = _deliver("plain", world, other, 2)
    assert (here.n_prefix, there.n_prefix) == want
    _same(here, there)
    assert here.plan_bounds[-1] == v >= here.n_delivered


@pytest.mark.parametrize("kind", ["plain", "pressure"])
@pytest.mark.parametrize("branch", ["prefix", "list"])
def test_a_cache_hit_on_the_tick_after_a_miss(kind, branch):
    """The cached permutation came from a sort of the same key: its
    front is the live entries on a hit as on a miss."""
    world = _world(kind, 200, seed=5)
    e = (_list_that_fits if branch == "prefix" else _list_that_does_not)(200)
    miss, _ = _deliver(kind, world, e, 1)
    plan = tuple(jnp.asarray(x) for x in (miss.plan_key, miss.plan_perm,
                                          miss.plan_bounds))
    hit, _ = _deliver(kind, world, e, 1, plan)
    assert hit.n_prefix == miss.n_prefix == (branch == "prefix")
    _same(hit, miss)
    np.testing.assert_array_equal(hit.plan_perm, miss.plan_perm)
    # and a plan of another tick's keys is a miss that takes its place
    stale = (plan[0].at[0].add(1), plan[1][::-1], plan[2] * 0)
    again, _ = _deliver(kind, world, e, 1, stale)
    _same(again, miss)
    np.testing.assert_array_equal(again.plan_perm, miss.plan_perm)


def test_fifo_through_ticks_that_alternate_branches():
    """16 ticks of 40 senders stamping their edges, a light tick (one
    stamp an edge: the prefix) then a heavy one (eight: the list) in
    turn. Four receivers have four in-edges each and overflow on every
    heavy tick; what they reject comes back through the spill on the
    next, light one. Every edge's stamps are taken out of its
    receiver's ring contiguous, across the spill and both branches. A
    sender with an entry in the spill sends nothing (it is muted until
    its spill drains: the rule FIFO rests on)."""
    kind, e, cap = "plain", 1024, CAPS["Actor"]
    short = prefix_len(e)
    senders = np.arange(40)
    edges = {s: [44 + s % 28] + ([40 + s // 4] if s < 16 else [])
             for s in senders}
    stamp = {(s, r): 0 for s in senders for r in edges[s]}
    last = dict.fromkeys(stamp, -1)
    world = _world(kind, 0, seed=8)
    world["tail"] = world["head"].copy()            # empty rings
    spill = (np.zeros(0, int), np.zeros(0, int), np.zeros((3, 0), int))
    took, spilled = [], 0
    for tick in range(16 + 8):
        burst = 0 if tick >= 16 else (1, 8)[tick % 2]
        parked = set(spill[1].tolist())
        fresh = [(r, s, stamp[s, r] + k)
                 for s in senders if s not in parked
                 for k in range(burst) for r in edges[s]]
        for r, s, _q in fresh:
            stamp[s, r] += 1
        tgt = np.array([r for r, _s, _q in fresh], int)
        snd = np.array([s for _r, s, _q in fresh], int)
        words = np.array([[1] * len(fresh), snd, [q for *_e, q in fresh]],
                         int).reshape(3, -1)
        # the spill first, oldest first, then the tick's sends
        world["tgt"] = np.concatenate([spill[0], tgt])
        world["sender"] = np.concatenate([spill[1], snd])
        world["words"] = np.concatenate([spill[2], words], axis=1)
        world["level"] = np.zeros(world["tgt"].shape[0], int)
        v = world["tgt"].shape[0]
        res, _ = _deliver(kind, world, e, tick)
        assert res.n_prefix == (0 < v <= short) and not res.spill_overflow
        took.append(int(res.n_prefix))
        keep = res.spill.tgt >= 0
        assert keep.sum() == res.spill_count == res.n_rejected
        spilled += int(res.spill_count)
        spill = (res.spill.tgt[keep], res.spill.sender[keep],
                 res.spill.words[:, keep])
        # every receiver empties its ring, in order
        buf, head = res.buf["Actor"], world["head"]
        for r in range(N):
            for slot in range(head[r], res.tail[r]):
                _gid, s, q = buf[slot % cap, :, r]
                assert q == last[s, r] + 1, (tick, s, r, q, last[s, r])
                last[s, r] = q
        world["buf"], world["tail"] = res.buf, res.tail
        world["head"] = res.tail.copy()
    assert took[:16] == [1, 0] * 8, took
    assert spilled >= 256
    assert last == {edge: n - 1 for edge, n in stamp.items()}
    assert min(stamp.values()) > 16 and not spill[0].size


# --------------------------------------------------- the whole runtime

def _ubench(cap, seeded, ticks):
    from ponyc_tpu.models import ubench
    opts = RuntimeOptions(mailbox_cap=cap, batch=4, max_sends=1,
                          msg_words=1, spill_cap=1024, inject_slots=8,
                          compile_cache="off", tuning_cache="off")
    rt, ids = ubench.build(1024, opts, pings=4)
    for n in seeded:
        ubench.seed_all(rt, ids[:n], 1000)
    rt.run(max_steps=ticks)
    return rt


def test_n_prefix_counts_exactly_the_prefix_ticks():
    """A list of 6,152 (1,024 pingers x 4 batch slots + spill + inject),
    a prefix of 1,664: a world that keeps 1,024 messages in flight takes
    the prefix on every tick, one that keeps 4,096 never, and the
    flight recorder's window record carries the count."""
    e = 1024 * 4 + 2 * 1024 + 8
    assert prefix_len(e) == 1664
    light = _ubench(16, [1024], 6)
    assert light.counter("n_prefix") == light.counter("step_no") == 6
    assert flight.latest().windows[-1]["n_prefix"] == 6
    # three more pings a pinger: 4,096 live from the next tick on
    from ponyc_tpu.models import ubench
    for _ in range(3):
        ubench.seed_all(light, np.arange(1024), 1000)
    light.run(max_steps=5)
    assert light.counter("step_no") == 11
    assert light.counter("n_prefix") == 6
    light.stop()
    heavy = _ubench(16, [1024] * 4, 5)
    assert heavy.counter("n_prefix") == 0 < heavy.counter("n_delivered")
    assert flight.latest().windows[-1]["n_prefix"] == 0
    heavy.stop()


def test_a_ring_of_one_block_holds_no_prefix(monkeypatch):
    """`mailbox_cap <= REBUILD_BLOCK`: no second length in the window,
    no counter in the state, no leaf in the aux — the program it was
    (the ring cell's tick is counted in operations). cosort likewise."""
    rt = _ubench(8, [64], 3)
    assert rt.state.route_counts == {} and rt._zero_aux.lists == {}
    assert rt.counter("n_prefix") == 0
    assert "n_prefix" not in flight.latest().windows[-1]
    rt.stop()

    def jaxpr(cap, cosort=False):
        w = _world("plain", 0)
        return str(jax.make_jaxpr(lambda: deliver(
            {"Actor": jnp.zeros((cap, 3, N), jnp.int32)},
            jnp.zeros(N, jnp.int32), jnp.zeros(N, jnp.int32),
            jnp.ones(N, bool),
            Entries(jnp.zeros(1024, jnp.int32), jnp.zeros(1024, jnp.int32),
                    jnp.zeros((3, 1024), jnp.int32)),
            n_local=N, mailbox_cap=cap, spill_cap=SPILL, overload_occ=cap,
            shard_base=jnp.int32(0), cohort_layout=w["layout"],
            cosort=cosort))())
    built = {(cap, cosort): jaxpr(cap, cosort)
             for cap, cosort in ((8, False), (16, True), (16, False))}
    monkeypatch.setattr(delivery, "prefix_len", lambda e: e)
    assert jaxpr(8) == built[8, False]
    assert jaxpr(16, cosort=True) == built[16, True]
    assert jaxpr(16) != built[16, False]
