"""Backpressure across `engine.tick` (≙ ponyint_sched_unmute_senders,
scheduler.c:1552-1635; ponyint_mute_actor + the mutemap,
actor.c:1171-1207): the world as the tick finds it (`world`), who is
released (`unmute_pass`, step 1), who is muted anew (`bookkeeping`, 5).
"""

from __future__ import annotations

from collections import namedtuple

import jax.numpy as jnp
from jax import lax

from ..ops.segment import counts_by_key
from .state import RtState, TickStatic, rows_of

# A row's status word for the unmute pass (`muter_bits`).
LIVE_CONG, CAN_RECOVER, RECOVERED, PRESSURED = 1, 2, 4, 8
# A row's word for the mute at routing time (`World.hot_global`, a mesh
# only): it declares pressure; it is overloaded — over its overload line
# as the tick finds it, or with messages parked for it in its shard's
# device spill.
HOT_PRESSURED, HOT_OVERLOADED = 1, 2
# This shard's index and the global id of its row 0; [nl] occupancy; world
# bits 1 and 2 of the previous vote; `hot_anywhere`: bit 0 or bit 3
# (someone declares pressure or is overloaded: the gate of `hot_global`'s
# gather and of the lookup in it, route._route_spill); [p * nl] declared
# pressure, mesh-wide (zeros where `hot_anywhere` is clear); [p * nl]
# int8 the HOT_* word of every row of the mesh (None on one chip; zeros
# where `hot_anywhere` is clear); [nl] messages parked for a row in the
# device spill.
World = namedtuple("World", "shard base occ0 muted_anywhere rspill_anywhere "
                   "hot_anywhere pressured_global hot_global dspill_pending")
Unmuted = namedtuple("Unmuted", "muted mute_refs mute_ovf")
# `became`: [nl] muted this tick and not before.
Muted = namedtuple("Muted", "became muted age refs ovf")


def world(k: TickStatic, st: RtState) -> World:
    p, nl = k.p, k.nl
    if p > 1:
        shard = lax.axis_index("actors").astype(jnp.int32)
    else:
        shard = jnp.int32(0)
    base = shard * nl
    occ0 = st.tail - st.head
    # World bits (previous tick's mesh-wide vote, stored replicated
    # per shard): bit0 = any actor pressured anywhere, bit1 = any
    # muted anywhere, bit2 = any route-spill entries anywhere, bit3 (a
    # mesh only) = any row overloaded anywhere. They
    # are shard-uniform by construction (computed from the packed
    # psum vote below; host writes set every shard's entry), so they
    # can gate collectives — every shard takes the same cond branch,
    # the same uniformity argument as the fused window's while cond.
    # This is the fork's whole thesis applied to the mesh
    # (README.md:8-10): a quiet world must not pay per-tick gather
    # latency for backpressure machinery it isn't using.
    wb0 = st.world_bits[0]
    world_pressured = (wb0 & 1) > 0
    world_muted = (wb0 & 2) > 0
    world_rspill = (wb0 & 4) > 0

    # The per-row pending histogram (a scatter-add, which serialises
    # on TPU) only runs when the spill actually holds messages — the
    # steady state skips it entirely.
    dspill_pending = lax.cond(
        st.dspill_count[0] > 0,
        lambda _: counts_by_key(
            jnp.minimum(jnp.maximum(st.dspill_tgt, 0), nl - 1),
            (st.dspill_tgt >= 0).astype(jnp.int32), nl),
        lambda _: jnp.zeros((nl,), jnp.int32), operand=None)

    # Who must not be sent to, mesh-wide (≙ ponyint_maybe_mute reading
    # the RECEIVER's flags on every send, whatever scheduler thread the
    # sender runs on, actor.c:898-921; pony_apply_backpressure being
    # visible to every scheduler): one all_gather of a word a row —
    # it declares pressure; it is overloaded as this tick finds it (over
    # its overload line, or messages parked for it in its shard's device
    # spill: what its own shard's delivery muted its LOCAL senders for
    # at the end of the tick before). Routing reads the word by the
    # sorted entries' targets (route._route_spill) and mutes their
    # senders, which are always local: a receiver's overload reaches a
    # sender on another shard one tick after it reached the senders on
    # its own. The remote unmute guard reads the pressured bit. Gated:
    # a tick of a mesh on which nobody declared pressure (world bit 0)
    # and nobody was overloaded (bit 3) when the last tick voted skips
    # the gather — zeros are exact.
    if p > 1:
        world_hot = world_pressured | ((wb0 & 8) > 0)
        overloaded = st.alive & (
            (occ0 > rows_of(k.program, "overload_occ"))
            | (dspill_pending > 0))
        hot = (jnp.where(st.pressured, HOT_PRESSURED, 0)
               | jnp.where(overloaded, HOT_OVERLOADED, 0)).astype(jnp.int8)
        hot_global = lax.cond(
            world_hot,
            lambda _: lax.all_gather(hot, "actors", tiled=True),
            lambda _: jnp.zeros((p * nl,), jnp.int8),
            operand=None)
        pressured_global = (hot_global & HOT_PRESSURED) > 0
    else:
        world_hot, hot_global = world_pressured, None
        pressured_global = st.pressured
    return World(shard, base, occ0, world_muted, world_rspill, world_hot,
                 pressured_global, hot_global, dspill_pending)


def unmute_pass(k: TickStatic, st: RtState, w: World) -> Unmuted:
    """--- 1. unmute pass (≙ ponyint_sched_unmute_senders,
    scheduler.c:1552-1635: receiver recovered → senders released, on
    every scheduler). A sender is released when EVERY receiver in its
    ref table has recovered — at or under its unmute line, nothing
    parked for it in its shard's device spill, no declared pressure —
    as this tick finds it. On a mesh a ref on another shard is asked
    the same bit of the same tick as a local one, from the one
    all-gather of the status word (`muter_bits`, under world bit 1), so
    a release is never stale; a remote ref additionally waits for this
    shard's route spill to be empty. (The MUTE that crosses shards is
    one tick late: `world`'s hot word, route._route_spill.) Link mutes,
    declared pressure, the aging release and its vetoes are below."""
    p, nl, opts = k.p, k.nl, k.opts
    base, occ0, pressured_global = w.base, w.occ0, w.pressured_global
    # One status word a row: everything the unmute pass asks of a
    # muting RECEIVER is decided here, over the rows, and the pass
    # reads it once by the mute refs (a gather is paid per index,
    # whatever it fetches). Bit 0: live-congested — shows congestion
    # evidence AND can run to drain it; bit 1: can-recover — alive
    # and unmuted, i.e. not itself deadlocked; bit 2: recovered —
    # drained to the unmute threshold, nothing parked for it in the
    # device spill, no declared pressure: what releases a sender it
    # muted; bit 3: declares pressure. The word is also the mesh's
    # one all-gather for the pass, so it is built OUTSIDE the unmute
    # cond (collectives must run collectively; jnp.any(st.muted) is
    # shard-local).
    can_recover = st.alive & ~st.muted
    calm = rows_of(k.program, "unmute_occ")     # a row's own unmute line
    live_cong = (((occ0 > calm) | (w.dspill_pending > 0))
                 & can_recover)
    recovered = ((occ0 <= calm) & (w.dspill_pending == 0)
                 & ~st.pressured)
    muter_bits = (jnp.where(live_cong, LIVE_CONG, 0)
                  | jnp.where(can_recover, CAN_RECOVER, 0)
                  | jnp.where(recovered, RECOVERED, 0)
                  | jnp.where(st.pressured, PRESSURED, 0)
                  ).astype(jnp.int32)
    # Gated like the pressured gather: the bits feed only the unmute
    # pass, which has work only when someone (anywhere) is muted —
    # exactly what world bit1 reports from the previous tick's vote.
    if p > 1:
        muter_bits_global = lax.cond(
            w.muted_anywhere,
            lambda _: lax.all_gather(muter_bits, "actors",
                                     tiled=True),
            lambda _: jnp.zeros((p * nl,), jnp.int32),
            operand=None)
    else:
        muter_bits_global = muter_bits

    def release_senders(_):
        # ≙ ponyint_sched_unmute_senders walking the mutemap
        # receiver-set (scheduler.c:1552-1635): a sender releases only
        # when EVERY tracked muting receiver has recovered.
        refs = st.mute_refs                       # [K, nl]
        has = refs >= 0
        lref = refs - base
        ref_local = (lref >= 0) & (lref < nl)
        status = muter_bits_global
        if p > 1:
            # Live-congested, can-recover and recovered as gathered
            # under world bit1 — set whenever this pass runs, so a
            # remote ref's `recovered` is its muter's own, as fresh as
            # a local ref's —, pressure from the hot word's all-gather
            # (world bit0), where it was believed before the word.
            status = ((status & (LIVE_CONG | CAN_RECOVER | RECOVERED))
                      | jnp.where(pressured_global, PRESSURED, 0))
        got = jnp.take(status, jnp.maximum(refs, 0), mode="clip")

        def says(bit):       # [K, nl]: the ref's muter has `bit` set
            return has & ((got & bit) > 0)
        ref_pressured = says(PRESSURED)
        recovered_ref = says(RECOVERED)
        local_ok = ref_local & recovered_ref
        # Remote muting ref: released when — and not before — the
        # receiver has recovered, by the same bit of the same tick as a
        # local ref (the gathered word), AND this shard's route-spill
        # has drained (a message parked here for that receiver is
        # congestion it cannot see yet). A remote receiver that still
        # DECLARES pressure holds its senders as a local one would
        # (`recovered` says so too; the bit is asked of the hot word,
        # which host-declared pressure reaches at once).
        remote_ok = (has & ~ref_local & (st.rspill_count[0] == 0)
                     & ~ref_pressured)
        if p > 1:       # one chip has no remote ref: its window stays
            remote_ok = remote_ok & recovered_ref      # what it was
        slot_ok = ~has | local_ok | remote_ok
        all_ok = jnp.all(slot_ok, axis=0)
        # Overflowed ref sets (more distinct muters than slots) defer
        # to a shard-wide quiet condition — conservative, never early.
        # Overflowed ref sets may have EVICTED a pressured ref
        # (slot collision), so the conservative release condition
        # consults the whole world's pressure bits, not just local.
        shard_quiet = (jnp.max(occ0) <= calm if isinstance(calm, int)
                       else jnp.all(occ0 <= calm)) \
            & (st.dspill_count[0] == 0) & (st.rspill_count[0] == 0) \
            & ~jnp.any(pressured_global)
        # Aging deadlock-breaker: a sender muted for
        # mute_age_limit consecutive ticks force-releases even if
        # its muters look unrecovered. Mutual-mute cycles and
        # chains (A muted-by B muted-by C...) can otherwise never
        # drain — the known deadlock of the reference's pre-0.36
        # backpressure, where every muter must RUN to recover and
        # muted actors don't run. Bounded queues + spill make the
        # periodic release safe: each release round dispatches real
        # work, and overflow still fails loudly. Host-declared
        # pressure is exempt (never aged away).
        # Staggered by actor row (threshold in [limit, 2*limit)):
        # a fan-in that muted thousands of senders on one tick would
        # otherwise release them all on one tick too, and the
        # synchronized wave into the still-full receiver could blow
        # the bounded spill. Phasing spreads releases over `limit`
        # ticks, so the per-tick wave is ~n_muted/limit.
        if opts.mute_age_limit > 0:
            lim = opts.mute_age_limit
            threshold = lim + jnp.arange(nl, dtype=jnp.int32) % lim
            aged = st.mute_age >= threshold
            held_by_pressure = jnp.any(ref_pressured, axis=0)
            # A tracked muter (on ANY shard — the word is the
            # mesh's all-gather) that still shows LIVE congestion
            # evidence (occ above the unmute threshold, or messages
            # parked in its shard's device spill) and that can still
            # run to drain it
            # (alive, not itself muted) vetoes aging: releasing a
            # sender into a receiver that is actively being worked
            # just grows the bounded spill until overflow — the
            # reference never releases while the muter is
            # overloaded/pressured (scheduler.c:1552-1635). Aging
            # therefore only breaks TRUE mute-cycle deadlocks, where
            # every congested muter is itself muted or dead and can
            # never run to recover. A non-empty local route spill
            # additionally holds any sender with a remote muter that
            # can still RECOVER (alive, unmuted): the backlog bound
            # for that muter is still in flight here, so its
            # congestion state is not yet observable. A remote muter
            # that is itself muted/dead gives no such hold — its
            # route-spill backlog can never drain (muted receivers
            # don't run), and holding on it would re-create the
            # cross-shard mute-cycle deadlock aging exists to break.
            held_by_live = jnp.any(says(LIVE_CONG), axis=0)
            if p > 1:
                remote_recover = jnp.any(
                    ~ref_local & says(CAN_RECOVER), axis=0)
                held_by_live = held_by_live | (
                    remote_recover & (st.rspill_count[0] > 0))
            # Overflowed ref sets may have EVICTED a pressured ref, so
            # aging defers while any pressure exists anywhere — the
            # same conservative rule as the non-aged ovf path.
            aged_ok = (aged & ~held_by_pressure & ~held_by_live
                       & (~st.mute_ovf | ~jnp.any(pressured_global)))
        else:
            # mute_age_limit <= 0: aging deadlock-breaker disabled
            # (reference mute semantics exactly — documented opt-out
            # in config.py).
            aged_ok = jnp.zeros((nl,), jnp.bool_)
        release = st.muted & (
            (all_ok & (~st.mute_ovf | shard_quiet))
            | aged_ok)
        return (st.muted & ~release,
                jnp.where(release[None, :], -1, refs),
                st.mute_ovf & ~release)

    # Nobody muted (the common case) → skip the pass entirely.
    return Unmuted(*lax.cond(
        jnp.any(st.muted), release_senders,
        lambda _: (st.muted, st.mute_refs, st.mute_ovf), operand=None))


def _merge_slots(a, b):
    both = (a >= 0) & (b >= 0)
    m = jnp.where(a < 0, b, jnp.where(b < 0, a, jnp.maximum(a, b)))
    return m, jnp.any(both & (a != b), axis=0)


def bookkeeping(st: RtState, life, res, routed) -> Muted:
    """--- 5. mute bookkeeping (≙ ponyint_mute_actor + mutemap insert,
    actor.c:1171-1207, mutemap.c): this tick's muting refs from
    delivery (`res`) and routing (`routed`) MERGE into each sender's slot
    table (a re-muted sender keeps its older muters); a slot collision
    between distinct refs sets the sticky overflow bit. `life`: the rows
    after the tick's destroys."""
    muted, mute_refs, mute_ovf = life.muted, life.mute_refs, life.mute_ovf
    newly = (res.newly_muted | routed.muted) & life.alive
    became_muted = newly & ~muted
    muted2 = muted | newly
    # Consecutive-muted-tick counter (see the aging release above):
    # +1 while muted, reset on release or fresh mute.
    mute_age2 = jnp.where(muted2,
                          jnp.where(became_muted, 0,
                                    st.mute_age + 1),
                          0)

    def merge_mutes(_):
        inc_refs, c1 = _merge_slots(res.new_mute_refs, routed.mute_refs)
        merged_refs, c2 = _merge_slots(mute_refs, inc_refs)
        return (jnp.where(newly[None, :], merged_refs, mute_refs),
                jnp.where(newly,
                          mute_ovf | res.new_mute_ovf | routed.mute_ovf
                          | c1 | c2,
                          mute_ovf))

    # The [K, N] slot-table merge only runs on ticks that actually
    # muted someone (≙ mutemap inserts happening only on mute).
    mute_refs2, mute_ovf2 = lax.cond(
        jnp.any(newly), merge_mutes,
        lambda _: (mute_refs, mute_ovf), operand=None)
    return Muted(became_muted, muted2, mute_age2, mute_refs2, mute_ovf2)
