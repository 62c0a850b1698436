"""Multi-receiver mute semantics (≙ mutemap.c + scheduler.c:1478-1635:
a receiver→set-of-muted-senders map; a sender unmutes only when *every*
muting receiver recovers).

The device design: each sender tracks up to K muting-receiver refs in
ref%K hash slots (state.mute_refs) with a sticky overflow bit for
collisions; the unmute pass releases a sender only when all tracked refs
have recovered (overflowed senders wait for a shard-quiet tick).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ponyc_tpu import I32, Ref, Runtime, RuntimeOptions, actor, behaviour
from ponyc_tpu.runtime.delivery import empty_mute_slots, mute_ref_slots


def test_mute_ref_slots_distinct_refs():
    n, k = 4, 4
    trig = jnp.array([True, True, False])
    rows = jnp.array([1, 1, 0], jnp.int32)
    refs = jnp.array([5, 6, 7], jnp.int32)      # 5%4=1, 6%4=2: no collision
    table, ovf = mute_ref_slots(trig, rows, refs, n=n, k=k)
    # table is [K slots, n senders] (planar; state.py layout note)
    assert table[1, 1] == 5 and table[2, 1] == 6
    assert not bool(ovf.any())
    assert (np.asarray(table)[:, 0] == -1).all()  # untriggered sender empty


def test_mute_ref_slots_collision_sets_overflow():
    n, k = 2, 4
    trig = jnp.array([True, True])
    rows = jnp.array([0, 0], jnp.int32)
    refs = jnp.array([3, 7], jnp.int32)         # both % 4 == 3: collide
    table, ovf = mute_ref_slots(trig, rows, refs, n=n, k=k)
    assert bool(ovf[0]) and not bool(ovf[1])
    assert table[3, 0] == 7                     # max kept


def test_mute_ref_slots_same_ref_twice_no_overflow():
    n, k = 2, 4
    trig = jnp.array([True, True])
    rows = jnp.array([0, 0], jnp.int32)
    refs = jnp.array([7, 7], jnp.int32)         # same receiver twice
    table, ovf = mute_ref_slots(trig, rows, refs, n=n, k=k)
    assert not bool(ovf.any())
    assert table[3, 0] == 7


@actor
class Slow:
    total: I32

    BATCH = 1          # deliberately slow consumer

    @behaviour
    def consume(self, st, v: I32):
        return {**st, "total": st["total"] + v}


@actor
class Fast:
    total: I32

    BATCH = 4          # recovers sooner than Slow

    @behaviour
    def consume(self, st, v: I32):
        return {**st, "total": st["total"] + v}


@actor
class Pusher:
    slow: Ref
    fast: Ref
    left: I32

    MAX_SENDS = 3

    @behaviour
    def produce(self, st, n: I32):
        self.send(st["slow"], Slow.consume, 1, when=n > 0)
        self.send(st["fast"], Fast.consume, 1, when=n > 0)
        self.send(self.actor_id, Pusher.produce, n - 1, when=n > 0)
        return {**st, "left": n - 1}


def _build(n_pushers=12, items=40):
    opts = RuntimeOptions(mailbox_cap=8, batch=2, msg_words=1,
                          max_sends=3, spill_cap=512, inject_slots=16)
    rt = Runtime(opts)
    rt.declare(Pusher, n_pushers).declare(Slow, 1).declare(Fast, 1)
    rt.start()
    slow = rt.spawn(Slow)
    fast = rt.spawn(Fast)
    ids = rt.spawn_many(Pusher, n_pushers, slow=slow, fast=fast)
    rt.bulk_send(ids, Pusher.produce, [items] * n_pushers)
    return rt, ids, slow, fast


def test_fanin_two_receivers_conservation_and_bounded_mutes():
    n_pushers, items = 12, 40
    rt, ids, slow, fast = _build(n_pushers, items)
    rt.run(max_steps=items * n_pushers * 8 + 200)
    assert rt.state_of(slow)["total"] == n_pushers * items
    assert rt.state_of(fast)["total"] == n_pushers * items
    assert not np.asarray(rt.state.muted).any(), "drained world still muted"
    # Mute volume sanity: release→burst→re-mute cycles are inherent to
    # lockstep backpressure (≙ the reference releasing a recovered
    # receiver's whole mutemap set at once), so mutes scale with items —
    # but never more than ~one mute per produced item. The *churn* the
    # multi-ref design eliminates (release while another muting receiver
    # is still hot) is checked exactly in
    # test_release_only_after_all_refs_recover.
    assert rt.counter("n_mutes") <= 2 * n_pushers * items, \
        rt.counter("n_mutes")


@actor
class Flooder:
    """Flood generator: each received ping fans two pings back at the
    peer (amplification 2, BATCH 1), keeping both mailboxes full with
    real traffic."""

    peer: Ref
    got: I32

    BATCH = 1
    MAX_SENDS = 2

    @behaviour
    def ping(self, st, v: I32):
        self.send(st["peer"], Flooder.ping, v - 1, when=v > 0)
        self.send(st["peer"], Flooder.ping, v - 1, when=v > 0)
        return {**st, "got": st["got"] + 1}


def _deadlocked_pair(mute_age_limit):
    """Build the TRUE mutual-mute deadlock: two actors with genuinely
    full mailboxes, each muted with the other as its (unrecovered,
    congested) muting ref. No release path exists except aging: each
    muter's occ stays above unmute_occ because the muter itself is
    muted and can never run to drain — the mute-cycle deadlock class
    the round-2 differential hunt (tests/hunt.py) found, which the
    reference's pre-0.36 backpressure shares.

    Live sends can't assemble this state directly (the reference's
    !OVERLOADED sender guard, delivery.py `~sender_hot`, keeps two
    mutually-hot actors from muting each other), so the flood runs
    until both queues are full of real traffic and the mute tables are
    then set to the cycle — a unit fixture for the unmute pass.
    """
    opts = RuntimeOptions(mailbox_cap=4, batch=1, msg_words=1,
                          max_sends=2, spill_cap=2048, inject_slots=8,
                          mute_age_limit=mute_age_limit)
    rt = Runtime(opts)
    rt.declare(Flooder, 2)
    rt.start()
    a = rt.spawn(Flooder)
    b = rt.spawn(Flooder, peer=a)
    rt.set_fields(Flooder, np.asarray([a]), peer=np.asarray([b]))
    rt.bulk_send(np.asarray([a, b]), Flooder.ping, np.asarray([8, 8]))
    inj = rt._empty_inject
    state = rt.state
    for _ in range(40):   # fill both rings with real messages
        state, aux = rt._step(state, *inj)
    occ = np.asarray(state.tail) - np.asarray(state.head)
    assert (occ > rt.opts.unmute_occ).all(), occ
    refs = np.full_like(np.asarray(state.mute_refs), -1)
    refs[b % rt.opts.mute_slots, a] = b       # a muted by b
    refs[a % rt.opts.mute_slots, b] = a       # b muted by a
    rt.state = dataclasses.replace(
        state,
        muted=jnp.ones_like(state.muted),
        mute_refs=jnp.asarray(refs),
        mute_age=jnp.zeros_like(state.mute_age))
    return rt, a, b


def test_aging_breaks_true_mute_cycle():
    """With aging on, the mutual-mute deadlock drains to completion."""
    rt, a, b = _deadlocked_pair(mute_age_limit=4)
    rt.run(max_steps=6000)
    assert not np.asarray(rt.state.muted).any(), "cycle never broken"
    occ = np.asarray(rt.state.tail) - np.asarray(rt.state.head)
    assert (occ == 0).all(), "queues not drained after release"


def test_mute_age_limit_zero_disables_aging():
    """mute_age_limit <= 0 = exact reference semantics: the mutual-mute
    cycle deadlocks forever (documented divergence opt-out)."""
    rt, a, b = _deadlocked_pair(mute_age_limit=0)
    got0 = int(np.asarray(rt.state.type_state["Flooder"]["got"]).sum())
    rt.run(max_steps=400)
    assert np.asarray(rt.state.muted).all(), \
        "deadlocked pair released with aging disabled"
    got = int(np.asarray(rt.state.type_state["Flooder"]["got"]).sum())
    assert got == got0, "deadlocked world advanced with aging disabled"


def _flood_pair_mesh(mute_age_limit):
    """Cross-shard twin of _deadlocked_pair, formed NATURALLY: the two
    Flooders live on different shards (1 row each) and the tiny route
    bucket's rejections route-mute BOTH of them against each other
    within a few ticks (full mailboxes, cross mute refs, route spill
    oscillating) — the cross-shard mutual-mute cycle, no state surgery
    required."""
    opts = RuntimeOptions(mailbox_cap=4, batch=1, msg_words=1,
                          max_sends=2, spill_cap=2048, inject_slots=8,
                          mute_age_limit=mute_age_limit, mesh_shards=2,
                          route_bucket=1, quiesce_interval=1)
    rt = Runtime(opts)
    rt.declare(Flooder, 2)
    rt.start()
    a = rt.spawn(Flooder)
    b = rt.spawn(Flooder, peer=a)
    rt.set_fields(Flooder, np.asarray([a]), peer=np.asarray([b]))
    rt.bulk_send(np.asarray([a, b]), Flooder.ping, np.asarray([8, 8]))
    inj = rt._empty_inject
    state = rt.state
    for _ in range(10):
        state, aux = rt._step(state, *inj)
    muted = np.asarray(state.muted)
    refs = np.asarray(state.mute_refs)
    assert muted.all(), f"pair not mutually route-muted: {muted}"
    assert b in refs[:, a] and a in refs[:, b], refs
    occ = np.asarray(state.tail) - np.asarray(state.head)
    assert (occ > rt.opts.unmute_occ).all(), occ
    rt.state = state
    return rt, a, b


def test_aging_breaks_cross_shard_mute_cycle():
    """A mutual-mute cycle SPANNING SHARDS (route-muted, undeliverable
    route spill) still drains under aging: a remote muter that can never
    recover gives no in-flight hold."""
    rt, a, b = _flood_pair_mesh(mute_age_limit=4)
    rt.run(max_steps=8000)
    assert not np.asarray(rt.state.muted).any(), \
        "cross-shard cycle never broken (rspill hold deadlock)"
    occ = np.asarray(rt.state.tail) - np.asarray(rt.state.head)
    assert (occ == 0).all(), "queues not drained after release"
    assert int(np.asarray(rt.state.rspill_count).sum()) == 0
    # All flood work ran to exhaustion: 2 seeds × (2^9 - 1) dispatches.
    got = int(np.asarray(rt.state.type_state["Flooder"]["got"]).sum())
    assert got == 2 * (2 ** 9 - 1), got


def test_cross_shard_cycle_freezes_without_aging_as_on_one_shard():
    """Like the single-shard cycle
    (test_mute_age_limit_zero_disables_aging), the CROSS-shard cycle
    freezes with aging disabled: a remote muting ref releases when — and
    not before — its receiver has recovered (mute.py remote_ok reads the
    gathered `recovered` bit), and each side's receiver is the other,
    full and muted. Until the mute crossed shards properly the remote
    ref was released whenever the local route spill had drained, and
    the pair ground on: that leak is what let a remote producer keep
    sending into an overloaded receiver (the meshed fan-in's spill
    overflow). Aging is what breaks the cycle, on one shard and across
    (test_aging_breaks_cross_shard_mute_cycle)."""
    rt, a, b = _flood_pair_mesh(mute_age_limit=0)
    got0 = int(np.asarray(rt.state.type_state["Flooder"]["got"]).sum())
    rt.run(max_steps=400)
    assert np.asarray(rt.state.muted).all(), \
        "cross-shard pair released with aging disabled"
    got = int(np.asarray(rt.state.type_state["Flooder"]["got"]).sum())
    assert got == got0 < 2 * (2 ** 9 - 1), (got0, got)


def test_aged_release_waits_for_live_congested_muter():
    """Sustained fan-in against a slow-but-runnable receiver: aging must
    NOT fire while the muting receiver shows live congestion evidence
    and can still run (advisor round-3 medium: unconditional aged
    release grows the bounded spill until overflow). The workload must
    throttle to completion under muting, exactly as the reference does."""
    n_pushers, items = 16, 50
    opts = RuntimeOptions(mailbox_cap=8, batch=2, msg_words=1,
                          max_sends=3, spill_cap=256, inject_slots=16,
                          mute_age_limit=2)   # aggressive aging
    rt = Runtime(opts)
    rt.declare(Pusher, n_pushers).declare(Slow, 1).declare(Fast, 1)
    rt.start()
    slow = rt.spawn(Slow)
    fast = rt.spawn(Fast)
    ids = rt.spawn_many(Pusher, n_pushers, slow=slow, fast=fast)
    rt.bulk_send(ids, Pusher.produce, [items] * n_pushers)
    inj = rt._empty_inject
    state = rt.state
    prev = None
    max_age_seen = 0
    for _ in range(3000):
        muted = np.asarray(state.muted)
        occ = np.asarray(state.tail) - np.asarray(state.head)
        refs = np.asarray(state.mute_refs)
        alive = np.asarray(state.alive)
        dsp = np.asarray(state.dspill_tgt)
        dsp_pending = np.zeros(rt.program.total, bool)
        dsp_pending[dsp[dsp >= 0]] = True
        if prev is not None:
            released = prev["muted"] & ~muted
            for s in np.nonzero(released)[0]:
                rs = prev["refs"][:, s]
                rs = rs[rs >= 0]
                live_congested = [
                    r for r in rs
                    if (prev["occ"][r] > opts.unmute_occ
                        or prev["dsp_pending"][r])
                    and prev["alive"][r] and not prev["muted"][r]]
                assert not live_congested, (
                    f"sender {s} released while muter(s) {live_congested} "
                    f"were runnable and still congested")
        max_age_seen = max(max_age_seen,
                           int(np.asarray(state.mute_age).max()))
        prev = dict(muted=muted, occ=occ, refs=refs, alive=alive,
                    dsp_pending=dsp_pending)
        state, aux = rt._step(state, *inj)
        assert not bool(aux.spill_overflow), \
            "aged releases blew the bounded spill"
        if not bool(aux.device_pending):
            break
    rt.state = state
    assert rt.state_of(slow)["total"] == n_pushers * items
    assert rt.state_of(fast)["total"] == n_pushers * items
    # Not vacuous: senders stayed muted well past the aging threshold
    # (limit=2 staggers thresholds into [2, 4)), i.e. aging was
    # age-eligible and the live-congestion veto is what held it.
    assert max_age_seen >= 2 * opts.mute_age_limit, max_age_seen


def test_aged_release_waits_cross_shard():
    """The mesh twin of the live-congestion aging veto: senders mute
    against a slow-but-runnable receiver on ANOTHER shard, whose
    congestion they can only see through the all-gathered live_cong
    bits. With aggressive aging (limit=2), no sender may be released
    while any of its tracked muters — local or remote — is alive,
    unmuted, and still congested (occ or pending spill)."""
    n_pushers, items = 32, 40
    opts = RuntimeOptions(mailbox_cap=8, batch=2, msg_words=1,
                          max_sends=3, spill_cap=4096, inject_slots=64,
                          mute_age_limit=2, mesh_shards=4,
                          quiesce_interval=1, route_bucket=8)
    rt = Runtime(opts)
    rt.declare(Pusher, n_pushers).declare(Slow, 1).declare(Fast, 1)
    rt.start()
    slow = rt.spawn(Slow)
    fast = rt.spawn(Fast)
    ids = rt.spawn_many(Pusher, n_pushers, slow=slow, fast=fast)
    rt.bulk_send(ids, Pusher.produce, [items] * n_pushers)
    p, nl = rt.program.shards, rt.program.n_local
    prev = None
    max_age_seen = 0
    cross_shard_mutes = 0
    for _ in range(4000):
        st = rt.state
        muted = np.asarray(st.muted)
        occ = np.asarray(st.tail) - np.asarray(st.head)
        refs = np.asarray(st.mute_refs)          # global ref ids
        alive = np.asarray(st.alive)
        ovf = np.asarray(st.mute_ovf)
        dsp = np.asarray(st.dspill_tgt).reshape(p, -1)
        pending = np.zeros(rt.program.total, bool)
        for s in range(p):
            loc = dsp[s][dsp[s] >= 0]
            pending[s * nl + loc] = True
        if prev is not None:
            released = prev["muted"] & ~muted
            for g in np.nonzero(released)[0]:
                if prev["ovf"][g]:
                    continue
                rs = prev["refs"][:, g]
                rs = rs[rs >= 0]
                local = rs[rs // nl == g // nl]
                remote = rs[rs // nl != g // nl]
                live = [r for r in rs
                        if (prev["occ"][r] > opts.unmute_occ
                            or prev["pending"][r])
                        and prev["alive"][r] and not prev["muted"][r]]
                # A live-congested LOCAL muter blocks every release path
                # (normal local_ok and the aged veto alike).
                assert not [r for r in live if r in local], (
                    f"sender {g} released past live local muter(s)")
                # With a remote ref and a non-empty local route spill,
                # neither remote_ok (spill not drained) nor aging (the
                # has_remote hold) may release. (With the spill drained,
                # remote_ok asks the remote receiver's own `recovered`
                # bit, as a local ref would: tests/test_fanin_mesh.py.)
                if len(remote) and prev["rspill"][g // nl] > 0:
                    raise AssertionError(
                        f"sender {g} released while its shard's route "
                        f"spill held {prev['rspill'][g // nl]} messages "
                        "(cross-shard aging veto hole)")
        for g in np.nonzero(muted)[0]:
            rs = refs[:, g]
            if any(r >= 0 and r // nl != g // nl for r in rs):
                cross_shard_mutes += 1
        max_age_seen = max(max_age_seen, int(np.asarray(st.mute_age).max()))
        prev = dict(muted=muted, occ=occ, refs=refs, alive=alive,
                    pending=pending, ovf=ovf,
                    rspill=np.asarray(st.rspill_count))
        rt.run(max_steps=1)
        if (rt.state_of(slow)["total"] == n_pushers * items
                and rt.state_of(fast)["total"] == n_pushers * items):
            break
    assert rt.state_of(slow)["total"] == n_pushers * items
    assert rt.state_of(fast)["total"] == n_pushers * items
    assert cross_shard_mutes > 0, "never saw a cross-shard mute ref"
    assert max_age_seen >= 2 * opts.mute_age_limit, max_age_seen
    rt.run(max_steps=100)
    assert not np.asarray(rt.state.muted).any()


def test_release_only_after_all_refs_recover():
    """Step manually; any sender released between ticks must have had
    every tracked muting receiver already recovered (or overflow+quiet)."""
    rt, ids, slow, fast = _build(8, 30)
    opts = rt.opts
    inj = rt._empty_inject
    state = rt.state
    prev = None
    releases_checked = 0
    for _ in range(300):
        muted = np.asarray(state.muted)
        occ = np.asarray(state.tail) - np.asarray(state.head)
        refs = np.asarray(state.mute_refs)
        ovf = np.asarray(state.mute_ovf)
        dsp = np.asarray(state.dspill_tgt)
        dsp_pending = np.zeros(rt.program.total, bool)
        dsp_pending[dsp[dsp >= 0]] = True
        if prev is not None:
            released = prev["muted"] & ~muted
            for a in np.nonzero(released)[0]:
                rs = prev["refs"][:, a]
                rs = rs[rs >= 0]
                if prev["ovf"][a]:
                    assert (prev["occ"] <= opts.unmute_occ).all()
                else:
                    assert (prev["occ"][rs] <= opts.unmute_occ).all(), \
                        (a, rs, prev["occ"][rs])
                    assert not prev["dsp_pending"][rs].any()
                releases_checked += 1
        prev = dict(muted=muted, occ=occ, refs=refs, ovf=ovf,
                    dsp_pending=dsp_pending)
        state, aux = rt._step(state, *inj)
        if not bool(aux.device_pending):
            break
    rt.state = state
    assert releases_checked > 0, "scenario never exercised a release"
    assert rt.state_of(slow)["total"] == 8 * 30

# ------------------------------------------- the unmute pass, bit by bit

@actor
class Quiet:
    """Takes items and sends nothing: a tick of a world of these mutes
    nobody, so what the unmute pass released is what the tick changed."""

    n: I32

    BATCH = 1

    @behaviour
    def take(self, st, v: I32):
        return {**st, "n": st["n"] + 1}


AGE_LIMIT = 4
# Global ids. On the mesh of two shards (four rows each) FAR_SENDER
# lives on the other shard than MUTER: its ref is a remote ref.
MUTER, BYSTANDER, SENDER, FAR_SENDER = 0, 3, 2, 5
_quiet_worlds = {}


def _quiet(shards):
    if shards not in _quiet_worlds:
        rt = Runtime(RuntimeOptions(mailbox_cap=8, batch=1, msg_words=1,
                                    max_sends=1, spill_cap=16,
                                    inject_slots=4, mesh_shards=shards,
                                    mute_age_limit=AGE_LIMIT))
        rt.declare(Quiet, 8).start()
        rt.spawn_many(Quiet, 8)
        # the step donates its state: every case starts from this copy
        _quiet_worlds[shards] = rt, jax.tree.map(np.asarray, rt.state)
    return _quiet_worlds[shards]


MUTER_STATES = [dict(zip(("over", "parked", "declared", "dead", "muted"),
                         map(bool, map(int, f"{i:05b}"))))
                for i in range(32)]
SENDERS = ["local-ref", "overflowed-set", "aged", "aged-overflowed",
           "two-refs"]
# behind a route-spill backlog a remote ref releases nobody, and ages
# nobody away from a muter that can still recover
WORLDS = [(1, s) for s in SENDERS] + [(2, s) for s in SENDERS] + [
    (2, "backlog"), (2, "aged-backlog")]


@pytest.mark.parametrize("shards,sender", WORLDS,
                         ids=lambda v: {1: "one-shard", 2: "mesh"}.get(v, v))
@pytest.mark.parametrize("muter", MUTER_STATES, ids=lambda m: "+".join(
    k for k, v in m.items() if v) or "recovered")
def test_unmute_pass_against_its_predicates_one_by_one(muter, shards,
                                                       sender):
    """The pass gathers one status word by the mute refs (recovered /
    pressured / live-congested / can-recover, engine `muter_bits`).
    Here every state a muting receiver can be in — queued over the
    unmute line, an item parked for it in the device spill, declared
    pressure, dead, itself muted — meets every kind of muted sender, on
    its own shard and on another, and the tick's `muted`, `mute_refs`
    and `mute_ovf` are held to the protocol's predicates, each read from
    its own table."""
    rt, st0 = _quiet(shards)
    opts, k = rt.opts, rt.opts.mute_slots
    n, nl = rt.program.total, rt.program.n_local
    shard = np.arange(n) // nl
    senders = [SENDER, FAR_SENDER]
    occ = np.zeros(n, np.int32)
    occ[MUTER] = opts.unmute_occ + (2 if muter["over"] else 0)
    pressured = np.zeros(n, bool)
    pressured[MUTER] = muter["declared"]
    alive = np.ones(n, bool)
    alive[MUTER] = not muter["dead"]
    muted = np.zeros(n, bool)
    muted[MUTER], muted[senders] = muter["muted"], True
    refs = np.full((k, n), -1, np.int32)
    refs[MUTER % k, senders] = MUTER
    if sender == "two-refs":              # the other muter has recovered
        refs[BYSTANDER % k, senders] = BYSTANDER
    ovf = np.zeros(n, bool)
    ovf[senders] = "overflowed" in sender
    age = np.zeros(n, np.int32)
    age[senders] = 2 * AGE_LIMIT if "aged" in sender else 0
    cap = st0.dspill_tgt.size // shards
    spill_tgt = np.full_like(st0.dspill_tgt, -1)     # local rows
    spill_tgt[shard[MUTER] * cap] = MUTER % nl if muter["parked"] else -1
    parked = np.zeros(n, np.int32)
    parked[MUTER] = muter["parked"]
    in_spill = np.bincount(shard, parked, shards)
    # one item for BYSTANDER waits in the route spill of FAR_SENDER's shard
    rspill_tgt = np.full_like(st0.rspill_tgt, -1)    # global ids
    in_route = np.zeros(shards, np.int32)
    if "backlog" in sender:
        rspill_tgt[shard[FAR_SENDER] * cap] = BYSTANDER
        in_route[shard[FAR_SENDER]] = 1

    # --- the protocol, predicate by predicate
    has = refs >= 0
    at = np.maximum(refs, 0)
    ref_local = shard[at] == shard[None, :]
    recovered = ((occ[at] <= opts.unmute_occ) & (parked[at] == 0)
                 & ~pressured[at])
    local_ok = has & ref_local & recovered
    remote_pressured = has & ~ref_local & pressured[at]
    # a remote ref is asked the same bit, and waits for the local route
    # spill besides
    remote_ok = (has & ~ref_local & recovered
                 & (in_route[shard] == 0)[None, :] & ~remote_pressured)
    can_recover = alive & ~muted
    live_congested = ((occ > opts.unmute_occ) | (parked > 0)) & can_recover
    held_by_pressure = (has & pressured[at]).any(axis=0)
    held_by_live = (has & live_congested[at]).any(axis=0)
    remote_recover = (has & ~ref_local & can_recover[at]).any(axis=0)
    held_by_live |= remote_recover & (in_route[shard] > 0)
    all_ok = (~has | local_ok | remote_ok).all(axis=0)
    occ_max = np.asarray([occ[shard == s].max() for s in range(shards)])
    shard_quiet = ((occ_max <= opts.unmute_occ) & (in_spill == 0)
                   & (in_route == 0) & ~pressured.any())[shard]
    aged = age >= AGE_LIMIT + np.arange(n) % nl % AGE_LIMIT
    aged_ok = aged & ~held_by_pressure & ~held_by_live \
        & (~ovf | (not pressured.any()))
    release = muted & ((all_ok & (~ovf | shard_quiet)) | aged_ok)
    # the case is the one its name says: the plain sender waits for its
    # muter while any of the three holds it, on the muter's shard and on
    # another alike — and a remote one behind a backlog besides
    if sender == "local-ref":
        waits = muter["over"] or muter["parked"] or muter["declared"]
        assert release[SENDER] == (not waits)
        if shards > 1:
            assert release[FAR_SENDER] == (not waits)
    if sender == "backlog":
        assert not release[FAR_SENDER]

    bits = pressured.any() | muted.any() << 1 | in_route.any() << 2
    state = dataclasses.replace(
        st0, head=np.zeros(n, np.int32), tail=occ, alive=alive,
        pressured=pressured, muted=muted, mute_refs=refs, mute_ovf=ovf,
        mute_age=age, dspill_tgt=spill_tgt,
        dspill_sender=np.full_like(st0.dspill_sender, -1),
        dspill_count=in_spill.astype(np.int32),
        rspill_tgt=rspill_tgt, rspill_count=in_route,
        world_bits=np.full_like(st0.world_bits, bits))
    after, _aux = rt._step(jax.tree.map(jnp.asarray, state),
                           *rt._empty_inject)
    np.testing.assert_array_equal(after.muted, muted & ~release)
    np.testing.assert_array_equal(after.mute_refs,
                                  np.where(release[None, :], -1, refs))
    np.testing.assert_array_equal(after.mute_ovf, ovf & ~release)
