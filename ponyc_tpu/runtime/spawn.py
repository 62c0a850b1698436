"""Rows and blob slots handed out ahead of `engine.tick`'s dispatch and
taken up after it (≙ pony_create's slot allocation, actor.c:688-734, and
pony_alloc on the owning actor's heap): steps 1b and 2a' (`reserve`), the
windows the dispatch loop asks for, 2b (`claim`) and the row pressure the
vote carries (`row_pressure`).
"""

from __future__ import annotations

from collections import namedtuple
from typing import Dict

import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import pack
from ..ops.segment import compact_mask, counts_by_key, marks_of
from .state import RtState, TickStatic, phase_scope, ring_take

# The device blob pool as the tick threads it; dispatch and the route
# replace `cur` = (data, used, len, gen) and add to the counts. `fail`:
# the pool ran out, `budget`: a BLOB_DISPATCHES budget did (both sticky);
# `free`: [blob_slots] compacted free global handles, `base`: the handle
# of this shard's slot 0 (None without a pool).
Pool = namedtuple("Pool", "cur fail budget n_alloc n_free n_remote free base")
# Target type -> its compacted free rows; [nl] alive and not muted.
Reserved = namedtuple("Reserved", "free_rows runnable pool")
Claimed = namedtuple("Claimed", "alive head tail0 n_spawned type_state")


def rspill_hits(k: TickStatic, base, rspill_tgt, any_rspill):
    """[nl] bool: rows some shard's route-spill still addresses.
    A message parked in *another shard's* route-spill may be
    addressed to a locally dead row; reclaiming that row would
    deliver the stale message to the newborn. One psum over the
    mesh makes every shard's rspill targets globally visible —
    the cross-shard twin of the dspill_pending guard. Gated on
    world bit2: with every shard's route-spill empty (the
    steady state) the psum is skipped and zeros are exact."""
    p, nl = k.p, k.nl
    if not (k.program.spawn_target_names and p > 1):
        return jnp.zeros((nl,), jnp.bool_)

    def _rhit(_):
        rhit = jnp.zeros((p * nl,), jnp.int32).at[
            jnp.maximum(rspill_tgt, 0)].max(
            (rspill_tgt >= 0).astype(jnp.int32), mode="drop")
        rhit = lax.psum(rhit, "actors")
        return lax.dynamic_slice(rhit, (base,), (nl,)) > 0
    return lax.cond(
        any_rspill, _rhit,
        lambda _: jnp.zeros((nl,), jnp.bool_), operand=None)


def free_mask(tc, alive_, occ_, pending_, rhit_):
    """A target cohort's claimable rows: dead, drained, nothing
    parked for the last tenant in any spill."""
    s0, s1 = tc.local_start, tc.local_stop
    return (~alive_[s0:s1] & (occ_[s0:s1] == 0)
            & (pending_[s0:s1] == 0) & ~rhit_[s0:s1])


def _windows(offset: int, run_c, rank, dispatches: int, sites: int):
    """A cohort's windows into a compacted free list, planar
    [dispatches, sites, rows]: the per-(dispatch, site) offsets are the
    small major axes, actor lanes minor."""
    widx = jnp.where(run_c, rank * (dispatches * sites), 0)
    return (offset + widx[None, None, :]
            + (jnp.arange(dispatches, dtype=jnp.int32)
               * sites)[:, None, None]
            + jnp.arange(sites, dtype=jnp.int32)[None, :, None])


def reserve(k: TickStatic, st: RtState, w, muted) -> Reserved:
    """--- 1b. spawn reservations (≙ pony_create's slot allocation,
    actor.c:688-734, done ahead of dispatch): per spawn-target
    cohort, compact this shard's free rows (dead, drained, no stale
    spill) and hand each spawner cohort its statically-partitioned
    window, reshaped to per-(actor, batch-slot, site) refs
    (`cohort_resv`); then 2a', the blob pool's slots likewise."""
    program, opts = k.program, k.opts
    free_rows: Dict[str, jnp.ndarray] = {}
    rspill_hit = rspill_hits(k, w.base, st.rspill_tgt, w.rspill_anywhere)
    for tname in program.spawn_target_names:
        tc = program.by_type_name(tname)
        with phase_scope("spawn/free"):
            perm, vfree, _ = compact_mask(
                free_mask(tc, st.alive, w.occ0, w.dspill_pending,
                          rspill_hit), tc.local_capacity)
            free_rows[tname] = jnp.where(
                vfree, tc.local_start + perm.astype(jnp.int32),
                jnp.int32(-1))

    runnable = st.alive & ~muted

    # --- 2a'. device blob pool reservations (the spawn-reservation
    # pattern applied to the "actor heap": compact this shard's free
    # pool slots, hand each allocating cohort its statically-
    # partitioned window; ≙ pony_alloc on the owning actor's heap,
    # done race-free ahead of the planar dispatch).
    free_blob = bbase = None
    if opts.blob_slots > 0:
        bsl = opts.blob_slots
        bbase = w.shard * bsl
        # Idle costs nothing (the fork's thesis, README.md:8-10):
        # the free-slot compaction feeds only reservation windows,
        # and no window is READ unless an allocating cohort
        # dispatches — so skip the sort when none has queued work.
        alloc_busy = jnp.bool_(False)
        allocating = False
        for _ch in program.device_cohorts:
            if _ch.blob_sites and _ch.blob_dispatches:
                allocating = True
                _sl = slice(_ch.local_start, _ch.local_stop)
                alloc_busy = alloc_busy | jnp.any(
                    runnable[_sl] & (w.occ0[_sl] > 0))

        def _compact_free(_):
            with phase_scope("dispatch/heap/reserve", when=allocating):
                bperm, bvfree, _n = compact_mask(~st.blob_used, bsl)
                return jnp.where(bvfree,
                                 bbase + bperm.astype(jnp.int32),
                                 jnp.int32(-1))
        free_blob = lax.cond(
            alloc_busy, _compact_free,
            lambda _: jnp.full((bsl,), -1, jnp.int32), operand=None)
    pool = Pool(
        cur=(st.blob_data, st.blob_used, st.blob_len, st.blob_gen),
        fail=st.blob_fail[0], budget=st.blob_budget_fail[0],
        n_alloc=jnp.int32(0), n_free=jnp.int32(0), n_remote=jnp.int32(0),
        free=free_blob, base=bbase)
    return Reserved(free_rows, runnable, pool)


def cohort_resv(ch, rs: Reserved, w):
    """Per-actor spawn reservations: the rows that can DISPATCH
    this tick (runnable and holding a message — only a dispatch
    can spawn) get disjoint spawn_dispatches × sites windows
    into the target's free rows, ranked by a cumsum over that
    mask. An idle row reserves nothing, whether it waits for a
    reply or is garbage the collector has not reached yet — see
    Program._resolve_spawns."""
    resv = {}
    if not ch.spawns:
        return resv
    s0, s1 = ch.local_start, ch.local_stop
    with phase_scope("spawn/reserve"):
        run_c = rs.runnable[s0:s1] & (w.occ0[s0:s1] > 0)
        rank = jnp.cumsum(run_c.astype(jnp.int32)) - 1
        for tname, sites in sorted(ch.spawns.items()):
            idx = _windows(ch.spawn_offsets[tname], run_c, rank,
                           ch.spawn_dispatches, sites)
            rows = jnp.take(rs.free_rows[tname], idx, mode="fill",
                            fill_value=-1)
            resv[tname] = jnp.where(
                (rows >= 0) & run_c[None, None, :],
                w.base + rows, jnp.int32(-1))
    return resv


def cohort_blob_resv(ch, rs: Reserved):
    """[bd, sites, rows] reserved global blob handles: each
    runnable actor gets blob_dispatches×sites disjoint windows
    into the compacted free list (idle actors reserve nothing);
    a used-counter walk hands one window to each dispatch that
    actually allocates (the spawn_dispatches pattern)."""
    sites = ch.blob_sites
    bd = ch.blob_dispatches
    if not sites:
        return jnp.zeros((bd, 0, ch.local_capacity), jnp.int32)
    with phase_scope("dispatch/heap/reserve"):
        run_c = rs.runnable[ch.local_start:ch.local_stop]
        rank = jnp.cumsum(run_c.astype(jnp.int32)) - 1
        handles = jnp.take(
            rs.pool.free, _windows(ch.blob_offset, run_c, rank, bd, sites),
            mode="fill", fill_value=-1)
        return jnp.where(run_c[None, None, :], handles, jnp.int32(-1))


def claim(k: TickStatic, st: RtState, w, d) -> Claimed:
    """--- 2b. apply spawn claims (before delivery, so constructor
    messages and same-step sends to the newborn land): claimed rows
    become alive with a fresh empty mailbox and zeroed state fields
    (the constructor behaviour initialises them — Pony's `create` is
    itself the first message)."""
    program, nl, base = k.program, k.nl, w.base
    claim_lists, init_lists, new_head = d.claim_lists, d.init_lists, d.head
    new_type_state = dict(d.type_state)
    alive = st.alive
    tail0 = st.tail
    n_spawned = jnp.int32(0)
    with phase_scope("spawn/claim"):
        for tname, clist in claim_lists.items():
            if not clist:
                continue
            refs = jnp.concatenate(clist)
            any_sync = any(e is not None for e in init_lists[tname])
            # Every claimed row is claimed once (the windows are
            # disjoint), so "which rows were claimed" is membership:
            # one mask (ops.segment.marks_of — a sort and a merge,
            # where a scatter of the claim list runs one update after
            # another), then selects over the rows.
            claimed = marks_of(jnp.where(refs >= 0, refs - base, -1), nl)
            alive = alive | claimed
            new_head = jnp.where(claimed, 0, new_head)
            tail0 = jnp.where(claimed, 0, tail0)
            n_spawned = n_spawned + jnp.sum(
                (refs >= 0).astype(jnp.int32))
            tc = program.by_type_name(tname)
            born = claimed[tc.local_start:tc.local_stop]
            ts = dict(new_type_state[tname])
            if any_sync:
                # Sync-constructed spawns (spawn_sync) land their
                # constructor's field values, claim by claim; cohorts
                # that never spawn_sync contribute constant-False
                # has-masks (the lanes cost only exists when some
                # behaviour of the program actually sync-constructs).
                cols = jnp.where(refs >= 0, refs - base - tc.local_start,
                                 tc.local_capacity)
                has_init = jnp.concatenate(
                    [e[0] if e is not None
                     else jnp.zeros((cl.shape[0],), jnp.bool_)
                     for e, cl in zip(init_lists[tname], clist)])
            for fname in ts:
                # async spawns zero and let the constructor message
                # initialise
                default = pack.null_word(tc.atype.field_specs[fname])
                ts[fname] = jnp.where(born, default, ts[fname])
                if any_sync:
                    vals = jnp.concatenate(
                        [e[1][fname] if e is not None
                         else jnp.zeros((cl.shape[0],), ts[fname].dtype)
                         for e, cl in zip(init_lists[tname], clist)])
                    ts[fname] = ts[fname].at[
                        jnp.where(has_init, cols, tc.local_capacity)
                    ].set(vals.astype(ts[fname].dtype), mode="drop")
            new_type_state[tname] = ts
    return Claimed(alive, new_head, tail0, n_spawned, new_type_state)


def spawning(k: TickStatic, ch):
    """Which behaviours of a spawner cohort hold a spawn site, by
    local behaviour index (verify's probe trace, at the window's
    trace like every other check of a behaviour's body): only a row
    about to dispatch one of them can be refused a row
    (StepAux.spawn)."""
    from .. import verify
    return np.array([bool(verify.behaviour_effects(
        b, ch.atype, msg_words=k.opts.msg_words,
        default_max_sends=k.opts.max_sends).spawns)
        for b in ch.behaviours])


def row_pressure(k: TickStatic, st: RtState, w, r, any_rspill_all, alive,
                 new_head, occ_after, n_spawned) -> dict:
    """Row pressure (a program with device spawns only; {} elsewhere):
    what the NEXT tick's reservations will find, read off this tick's
    final state with the next tick's own predicates: the free rows by
    free_mask; the rows that will reserve, those that hold a
    message (muted or not: an unmute may release them first). A
    row can be refused only when it dispatches a behaviour that
    spawns, so a spawner cohort needs its window up to the LAST
    such row: spawn_offset + (that row's rank among the reserving
    rows + 1) × spawn_dispatches × sites."""
    program, p, nl, res = k.program, k.p, k.nl, r.res
    if not program.has_device_spawns:
        return {}
    with phase_scope("spawn/reserve"):
        pending2 = lax.cond(
            res.spill_count > 0,
            lambda _: counts_by_key(
                jnp.minimum(jnp.maximum(res.spill.tgt, 0), nl - 1),
                (res.spill.tgt >= 0).astype(jnp.int32), nl),
            lambda _: jnp.zeros((nl,), jnp.int32), operand=None)
        rhit2 = rspill_hits(k, w.base, r.rspill.tgt, any_rspill_all)
        n_free = {
            t: jnp.sum(free_mask(
                program.by_type_name(t), alive, occ_after, pending2,
                rhit2).astype(jnp.int32))
            for t in program.spawn_target_names}
        room = jnp.int32(2**31 - 1)
        for ch in program.device_cohorts:
            if not ch.spawns:
                continue
            s0, s1 = ch.local_start, ch.local_stop
            holds = alive[s0:s1] & (occ_after[s0:s1] > 0)
            may = spawning(k, ch)
            wants = holds
            if not may.all():
                # some behaviour never spawns: ask the messages
                # the next dispatch will take which they are
                gid0 = ch.behaviours[0].global_id
                gids = res.buf[ch.atype.__name__][:, :1, :]
                wants = jnp.zeros_like(holds)
                for j in range(ch.batch):
                    beh = ring_take(
                        gids, (new_head[s0:s1] + j)
                        % ch.mailbox_cap)[0] - gid0
                    wants = wants | (
                        (j < occ_after[s0:s1])
                        & (beh >= 0) & (beh < len(may))
                        & jnp.asarray(may)[
                            jnp.clip(beh, 0, len(may) - 1)])
                wants = wants & holds
            last = jnp.max(jnp.where(
                wants, jnp.cumsum(holds.astype(jnp.int32)), 0))
            for tname, sites in ch.spawns.items():
                room = jnp.minimum(room, n_free[tname] - (
                    ch.spawn_offsets[tname] * (last > 0)
                    + last * ch.spawn_dispatches * sites))
        born = st.n_spawned[0] + n_spawned
        if p > 1:
            room = lax.pmin(room, "actors")
            born = lax.psum(born, "actors")
    return {"room": room,
            "low": jnp.where(room >= 0, room,
                             jnp.int32(2**31 - 1)),
            "spawned": born}
