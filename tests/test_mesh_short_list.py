"""A meshed shard delivers over what arrived (route.deliver_routed, step 4):
where a tick's arrivals fit one shard's outbox the received buckets are
joined front to front and delivery runs over the SHORT list, else over
the received buckets as they came, the LONG list it always ran. On the
suite's virtual CPU devices:

  - a uniform world takes the short list on every shard of every tick
    and is, leaf for leaf, the world that has the long list only (the
    static guard `route._unpack_fits` patched to refuse the short one);
  - a world whose stamped messages all land on shard 0, in pulses,
    takes the long list there on the ticks of a pulse and the short one
    between them, loses, doubles and reorders nothing, never overflows
    a spill, and is again the long-only world tick by tick;
  - the cached plan keeps the long list's shape and belongs to one
    length at a time: cycle traffic hits it on the short list from the
    second tick on, and neither length validates what the other stored.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ponyc_tpu import I32, Ref, Runtime, RuntimeOptions, actor, behaviour
from ponyc_tpu.runtime import delivery, route
from ponyc_tpu.runtime.state import layout_sizes
from chip_smoke import PLAN_CACHE_LEAVES
from test_mesh_ubench import TICKS, _world


def _leaves(rt):
    """Every state leaf by path, but the plan's three arrays (they hold
    one list length's plan or the other's) and the counter of the
    choice itself."""
    flat, _ = jax.tree_util.tree_flatten_with_path(rt.state)
    out = {jax.tree_util.keystr(path): np.asarray(leaf)
           for path, leaf in flat}
    return {k: v for k, v in out.items()
            if not any(p in k for p in PLAN_CACHE_LEAVES) and "n_unpacked" not in k}


def _assert_same_world(a, b, tick):
    la, lb = _leaves(a), _leaves(b)
    assert la.keys() == lb.keys()
    bad = [k for k in la if not np.array_equal(la[k], lb[k])]
    assert not bad, (tick, bad[:6])


def _long_only(monkeypatch):
    monkeypatch.setattr(route, "_unpack_fits", lambda *_a: False)


@pytest.mark.parametrize("shards,recipients", [(4, "random"), (2, "cycle")])
def test_a_uniform_world_is_the_long_lists_world(shards, recipients,
                                                 monkeypatch):
    """Mailboxes, heads, tails, spills, mutes, counters: every leaf of
    the short list's world equals the long-only world's after every
    tick, and every shard of every tick took the short list."""
    short = _world(shards, recipients).rt
    with monkeypatch.context() as patched:
        _long_only(patched)
        long_ = _world(shards, recipients).rt
        assert long_.run(max_steps=1) == 0       # traced under the patch
    assert short.run(max_steps=1) == 0
    for tick in range(1, TICKS):
        _assert_same_world(short, long_, tick)
        assert short.run(max_steps=1) == 0
        assert long_.run(max_steps=1) == 0
    _assert_same_world(short, long_, TICKS)
    assert short.counter("n_unpacked") == shards * TICKS
    assert long_.counter("n_unpacked") == 0
    assert short.counter("n_routed") == long_.counter("n_routed") > 0
    short.stop()
    long_.stop()


# --- every stamped message lands on shard 0, in pulses -----------------

SHARDS, SOURCES, SINKS, PULSES = 4, 128, 4, 4


@actor
class Sink:
    """Takes stamps from lockstep sources: what it dispatches must be
    every stamp 0, 1, 2, ... in order (all of one stamp before the
    next), each once."""
    last: I32
    bad: I32
    got: I32
    total: I32

    BATCH = 16
    MAX_SENDS = 1

    @behaviour
    def take(self, st, seq: I32):
        ok = (seq == st["last"]) | (seq == st["last"] + 1)
        return {**st, "last": seq, "got": st["got"] + 1,
                "total": st["total"] + seq,
                "bad": st["bad"] + np.int32(1) * ~ok}


@actor
class Source:
    """A self-send chain that stamps its sink on every other link."""
    sink: Ref["Sink"]
    seq: I32

    BATCH = 1
    MAX_SENDS = 2

    @behaviour
    def pulse(self, st, n: I32):
        fire = (n > 0) & (n % 2 == 0)
        self.send(st["sink"], Sink.take, st["seq"], when=fire)
        self.send(self.actor_id, Source.pulse, n - 1, when=n > 0)
        return {**st, "seq": st["seq"] + np.int32(1) * fire}


def _pulsed_world():
    """128 sources dealt over four shards, 4 sinks on shard 0. On a
    pulse shard 0 is sent 128 stamps and its own 32 chain links, 160
    entries for a short list of 144 (spill 16 + outbox 128): the long
    list; between pulses 32: the short one. The other shards only ever
    receive their own 32 links."""
    opts = RuntimeOptions(mailbox_cap=64, batch=1, max_sends=2,
                          msg_words=1, spill_cap=16, inject_slots=8,
                          mesh_shards=SHARDS, quiesce_interval=1,
                          compile_cache="off", tuning_cache="off")
    rt = Runtime(opts)
    rt.declare(Source, SOURCES).declare(Sink, SHARDS * SINKS)
    rt.start()
    sinks = rt.spawn_many(Sink, SHARDS * SINKS,
                          last=np.full(SHARDS * SINKS, -1, np.int32))
    sinks = sinks[np.asarray(sinks) // rt.program.n_local == 0]
    assert sinks.shape[0] == SINKS
    sources = rt.spawn_many(Source, SOURCES,
                            sink=sinks[np.arange(SOURCES) % SINKS])
    rt.bulk_send(sources, Source.pulse,
                 np.full(SOURCES, 2 * PULSES, np.int32))
    return rt


def _sizes(rt):
    e_out, bucket, e_long = layout_sizes(rt.program, rt.opts)
    l_in = rt.opts.spill_cap + e_out
    return l_in, bucket, e_long, rt.opts.spill_cap + rt.opts.inject_slots + l_in


def _count_plan_sorts(monkeypatch):
    """Every execution of a plan miss's sort, on any shard, lands in
    the returned list (a host callback inside the miss branch)."""
    ran = []
    real = delivery.stable_sort_with_keys

    def counting(key):
        jax.debug.callback(lambda: ran.append(1))
        return real(key)
    monkeypatch.setattr(delivery, "stable_sort_with_keys", counting)
    return ran


def test_a_world_skewed_onto_one_shard_takes_the_long_list_there(
        monkeypatch):
    rt = _pulsed_world()
    l_in, bucket, e_long, e_short = _sizes(rt)
    assert (l_in, bucket) == (144, 144)
    assert route._unpack_fits(SHARDS, bucket, l_in)
    with monkeypatch.context() as patched:
        _long_only(patched)
        twin = _pulsed_world()
        assert twin.run(max_steps=1) == 0
    ran = _count_plan_sorts(monkeypatch)
    assert rt.run(max_steps=1) == 0
    took_long, ticks, before = [], 1, np.zeros(SHARDS, np.int64)
    while True:
        _assert_same_world(rt, twin, ticks)
        unpacked = np.asarray(rt.state.route_counts["n_unpacked"])
        short_now = (unpacked - before).astype(bool)
        before = unpacked
        took_long.append(not short_now[0])
        assert short_now[1:].all(), ticks       # their own links only
        # the plan in the state is this tick's list's: a short tick
        # left the mark behind its key, a long one overwrote it
        marks = np.asarray(rt.state.plan_key).reshape(
            SHARDS, e_long)[:, e_short]
        np.testing.assert_array_equal(marks < 0, short_now)
        if not (np.asarray(rt.state.tail) - np.asarray(rt.state.head)).any():
            break
        sorts = len(ran)
        assert rt.run(max_steps=1) == 0         # never a spill overflow
        assert twin.run(max_steps=1) == 0
        jax.effects_barrier()
        if ticks < 2 * PULSES:
            # shard 0 changes list on every tick of the pulses: the
            # plan it finds is the other length's, so it sorts
            assert len(ran) > sorts, ticks
        ticks += 1
    # the pulses' ticks, and no other, ran the long list on shard 0
    assert took_long[:2 * PULSES] == [True, False] * PULSES
    assert not any(took_long[2 * PULSES:])
    assert rt.counter("n_unpacked") == SHARDS * ticks - PULSES
    # exactly once and in order
    sinks = rt.cohort_state(Sink)
    wired = np.flatnonzero(np.asarray(sinks["got"]))
    assert wired.shape[0] == SINKS
    per_sink = SOURCES // SINKS
    assert (np.asarray(sinks["got"])[wired] == per_sink * PULSES).all()
    assert (np.asarray(sinks["total"])[wired]
            == per_sink * sum(range(PULSES))).all()
    assert not np.asarray(sinks["bad"]).any()
    assert (np.asarray(rt.cohort_state(Source)["seq"])[:SOURCES]
            == PULSES).all()
    for name in ("n_rejected", "n_deadletter", "n_badmsg", "n_mutes"):
        assert rt.counter(name) == 0, name
    assert not np.asarray(rt.state.rspill_count).any()
    rt.stop()
    twin.stop()


def test_cycle_traffic_hits_the_plan_cache_on_the_short_list(monkeypatch):
    """Topology-stable traffic on a mesh: one plan a shard, sorted on
    the first tick that has the cycle's messages in flight, then the
    key compare alone — on the short list, every shard of every tick."""
    shards = 4
    ran = _count_plan_sorts(monkeypatch)
    rt = _world(shards, "cycle").rt
    sorts = []
    for _tick in range(6):
        assert rt.run(max_steps=1) == 0
        jax.effects_barrier()
        sorts.append(len(ran))
    assert sorts[0] == shards, sorts              # every shard planned
    assert sorts[1:] == [sorts[1]] * 5, sorts     # and never again
    assert rt.counter("n_unpacked") == shards * 6
    rt.stop()


# --- the plan arrays, one list length at a time ------------------------

def test_a_plan_never_validates_the_other_lengths_list():
    e_long, e_short, rows = 24, 9, 5
    rng = np.random.default_rng(3)
    fresh = (jnp.full((e_long,), -1, jnp.int32),
             jnp.zeros((e_long,), jnp.int32),
             jnp.zeros((rows + 1,), jnp.int32))
    # a fresh state matches nothing, on either list
    key, perm, _ = route._short_plan(fresh, e_short)
    assert key.shape == perm.shape == (e_short,) and (np.asarray(key) < 0).all()

    key_s = jnp.asarray(rng.integers(0, rows + 1, e_short), jnp.int32)
    perm_s = jnp.asarray(rng.permutation(e_short), jnp.int32)
    key_l = jnp.asarray(rng.integers(0, rows + 1, e_long), jnp.int32)
    key_l = key_l.at[:e_short].set(key_s)         # the worst case: the
    perm_l = jnp.asarray(rng.permutation(e_long), jnp.int32)  # same front

    # short stores: a short tick finds its plan, a long one a mismatch
    key, perm = route._store_short_plan(fresh, key_s, perm_s)
    assert key.shape == perm.shape == (e_long,)
    got = route._short_plan((key, perm, fresh[2]), e_short)
    np.testing.assert_array_equal(got[0], key_s)
    np.testing.assert_array_equal(got[1], perm_s)
    assert not bool(jnp.all(key_l == key))        # the mark: no key is < 0

    # long stores (deliver's own arrays): a short tick whose key IS the
    # long key's front must still replan, not take the front of a
    # permutation of the long list
    got = route._short_plan((key_l, perm_l, fresh[2]), e_short)
    assert not bool(jnp.all(got[0] == key_s))
    # and a short store over a long plan leaves the rest as it was
    key, perm = route._store_short_plan((key_l, perm_l, fresh[2]),
                                         key_s, perm_s)
    np.testing.assert_array_equal(key[e_short + 1:], key_l[e_short + 1:])
    np.testing.assert_array_equal(perm[e_short:], perm_l[e_short:])
    assert int(key[e_short]) == -1
