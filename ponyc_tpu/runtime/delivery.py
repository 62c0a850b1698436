"""Batched message delivery: the vectorised send path (shard-local).

≙ the reference's pony_sendv → ponyint_maybe_mute → messageq_push →
ponyint_sched_add chain (src/libponyrt/actor/actor.c:773-968,
actor/messageq.c:102-160), executed for *every in-flight message at once*
within one shard of the actor world:

  1. the engine hands over all candidate messages for this tick whose
     target rows live on this shard — receiver-side spill (oldest first),
     host injections, then freshly routed/produced messages;
  2. stable-sort by target row: per-target arrival order is then
     [older spill → inject → new-in-emission-order], which preserves the
     per-sender→receiver FIFO guarantee Pony gives (messageq FIFO + causal
     send order; SURVEY.md §7 hard part (c)) because a sender whose message
     was rejected is muted until its spill drains, so it can never emit a
     *newer* message that would overtake an older spilled one;
  2b. the sort packs the live entries; what follows runs over them. An
     entry that is not delivered (an empty slot, a target off this
     shard) has the largest key there is, so the stable sort leaves
     the tick's V live entries as the first V of the permutation, in
     delivery order, and nothing in points 3-5 reads a position past V.
     A delivery list is sized for what its world COULD send (every
     actor, every batch slot, every send site) and is mostly room in a
     world where few send or where a behaviour has more sites than
     fire: `deliver` therefore holds points 3-5 at two static lengths,
     the whole list and `prefix_len` of it (a quarter), and the tick's
     own count chooses, `V <= prefix` — two conditionals in turn (the
     whole list's tick, or the prefix's words, or nothing; then the
     prefix's tick: one table-writing branch each, see there), and one
     inside the plan's miss for the bounds' merge. The permutation
     gather, the merge, the table the
     rebuild's gathers read and every list read of the pressure branch
     are then paid by a quarter of the slots. Same mailboxes, spill,
     mutes and stored plan on either branch, bit for bit
     (tests/test_delivery_prefix.py); `n_prefix` (RtState.route_counts)
     counts the ticks that took the prefix. What makes the prefix is
     not shortened: the key, its compare with the cached one, the sort.
     Not built for rings of one block (4d) nor in cosort;
  3. per-target segment bounds come from merging the target boundaries
     into the sorted keys (ops/segment.py `segment_bounds`: sorts and a
     prefix sum, no indexed read); each target accepts min(count,
     free-space), so rejections are always the newest suffix per target,
     keeping FIFO safe. What is a property of the target ROW is decided
     here, over the rows, never per entry of the list: a dead row
     accepts nothing and its segment is the tick's dead letters; "does
     anything target a pressured actor" is asked of the counts;
  4. the mailbox table is rebuilt by ARRIVAL RANK, in blocks: block k
     pulls sorted entries seg_start + r for the REBUILD_BLOCK ranks
     r = k*B .. k*B+B-1, and rank r lands in ring slot (tail + r) % cap
     where r < accepted. A COHORT's tables run only as many blocks as
     that cohort's fullest mailbox of the tick needs (`max(acc)` over
     its rows; none where it received nothing), and a block pulls only
     for the rows that have a message in it, those with acc > k*B.
     While more than M = ceil(rows / B) of the cohort's rows are that
     deep the block runs FULL WIDTH: every row's rank, one gather of
     `rows` indices a rank — for the min(B, max(acc) - k*B) ranks some
     row of the cohort holds, in a loop of its own, and no more: a
     cohort whose every actor sends itself one message a tick (a
     producer, a streamer, a timer, a body of a ring) pulls one rank
     for its first block, not eight, and what the loop pulls is placed
     in one pass as before. From the first block whose rows fit in M on
     — their count only falls with k, so a cohort is two
     `lax.while_loop`s in turn and no `cond` — a block runs COMPACTED:
     the deep rows' indices ascending, each with its segment's start
     (one sort of the rows), B*M indices into the list, then one
     scatter of the M windows back to their rows' lanes, sorted and
     unique and told so: B*M = rows indices for what a full block of B
     ranks reads in B*rows. The gather is the expensive part —
     ~6 ns an index on a v5e whatever it fetches; a scatter told sorted
     and unique ~40 ns a window, untold one update after another (note
     a) — so a steady world pays max(acc)*rows a tick instead of cap*N,
     a million shallow senders do not pay for the one deep receiver they
     share a world with, the few rows past rank 8 of a Poisson tail do
     not make every row gather another block, and a world few of whose
     rows receive anything compacts its first block too (k = 0 is no
     special case).
     TPU-first design notes: (a) XLA lowers large scatters to serial
     loops on TPU, so the one scatter the CPU-obvious design would use
     was the whole step's bottleneck — the gather form is fully
     vectorised; (b) the mailbox table is laid out [cap, words, N] with
     the actor axis minor-most, so each plane op is a full-width
     128-lane vector op and the per-block pull from the sorted entries
     is a plain 1-D lane gather (see state.py's layout note — the
     actor-major form ran ~30× slower on real TPU from tile padding);
     (c) a pulled rank is PLACED with a select chain over the table
     (`where(slot's rank == r, pulled[r], table)`, B selects XLA fuses
     into one pass at memory speed), never with `take_along_axis` over
     the slot axis: that lowers to the same per-index gather over all
     cap*N slots the blocks exist to avoid; (d) a ring no deeper than
     one block (`mailbox_cap <= REBUILD_BLOCK`) is rebuilt slot-plane by
     slot-plane in one gather with no depth reduction and no loop — the
     same indices, and the idle tick of a small world is counted in
     operations;
  5. rejections compact into the next spill buffer and their locally
     resident senders mute (≙ ponyint_maybe_mute: mute on sending to an
     overloaded/muted receiver, actor.c:898-921; on a mesh the senders
     on OTHER shards mute a tick later, at routing, by the mesh-wide
     hot word — route._route_spill — so such a sender has at most two
     messages outside a mailbox where a local one has one). Both are *pressure
     paths*: they run under `lax.cond` and cost nothing in the steady
     state where nothing rejects and nobody is overloaded (≙ the
     reference only walking mute maps when senders actually muted,
     scheduler.c:1478-1494). Inside the branch the list asks each ROW
     one thing at a time and reads one word for it: an index vector of
     E entries costs the same whatever it fetches, so four questions of
     one index are four gathers, and what can be answered over the N
     rows first is one. A TARGET answers `bound` = seg_start + accepted,
     the sorted position where its rejected suffix begins (entry i is
     rejected iff i >= bound — no rank, no second table), and `hot_t` =
     over the overload threshold after this tick or declared pressure;
     a dead row accepts its whole segment and is never hot. A SENDER
     answers the one bit `hot_s` (over the threshold or declared
     pressure: the exemption from muting). `bound` and `hot_t` also
     pack into one word, `(bound << 1) | hot_t`, read once: equal on
     every leaf and its gather as fast as either, but the executable
     the v5e's compiler built round it put the rebuild's gathers in
     slower memory and the tick read slower (PERF.md §6, PR 32), so two
     reads by the target shipped. Who was muted is read off the ref
     table the triggers were scattered into, not scattered a second
     time. The unmute pass (mute.py `muter_bits`) reads its muting
     receivers the same way: one status word a row, gathered once by
     the mute refs.
"""

from __future__ import annotations

from functools import partial
from itertools import accumulate
from typing import NamedTuple

import jax.numpy as jnp
from jax import lax

from ..ops.segment import (compact_mask, segment_bounds,
                           stable_sort_with_keys)
from .state import REBUILD_BLOCK, phase_scope


class Entries(NamedTuple):
    """A flat batch of in-flight messages (targets in *local rows* here;
    the routing layer in route.py deals in global ids)."""
    tgt: jnp.ndarray      # [E] int32 target row; -1 = empty slot
    sender: jnp.ndarray   # [E] int32 sender *global* id; -1 = host/no sender
    words: jnp.ndarray    # [1+W, E] int32 (word0 = behaviour gid)


class DeliveryResult(NamedTuple):
    buf: dict                  # {type: [cap, 1+W_c, rows_c]} per cohort
    trace_buf: dict            # {type: [cap, 2, rows_c]} causal-trace
    #                               side lanes (tracing on only; {}
    #                               when off) — rebuilt with the SAME
    #                               gather as buf so a delivered
    #                               message and its context can never
    #                               land in different slots
    tail: jnp.ndarray
    spill: Entries             # rejected entries, compacted, oldest first
    spill_count: jnp.ndarray   # [] int32
    spill_overflow: jnp.ndarray
    newly_muted: jnp.ndarray   # [n_local] bool (local senders only)
    new_mute_refs: jnp.ndarray  # [K, n_local] global refs slotted by
    #                               ref % K (-1 = empty)
    new_mute_ovf: jnp.ndarray  # [n_local] bool — distinct refs collided
    #                               in one slot this tick
    n_delivered: jnp.ndarray
    n_rejected: jnp.ndarray
    n_deadletter: jnp.ndarray
    plan_key: jnp.ndarray      # [E] the key vector this plan sorts
    plan_perm: jnp.ndarray     # [E] cached stable-sort permutation
    plan_bounds: jnp.ndarray   # [n_local+1] cached segment bounds
    rebuild_slots: jnp.ndarray  # [] int32 indices the rebuild's gathers
    #                               read this tick: over the cohorts and
    #                               the rank blocks ITS fullest mailbox
    #                               made it run, the ranks that mailbox
    #                               holds of the block (B, or what is
    #                               left of max(acc)) x its rows a
    #                               full-width block, B ranks x M a
    #                               compacted one (rebuild_tables); cap x
    #                               N for a ring of one block; 0 with no
    #                               message
    n_prefix: object = None    # [] int32, 1 where this tick ran over the
    #                               list's PREFIX (deliver; 0 always in
    #                               cosort and for a list of a few
    #                               tiles, which have the one length);
    #                               None for rings of one rebuild
    #                               block: the state has no such counter


def mute_ref_slots(trig, mute_row, refs, *, n: int, k: int):
    """Scatter triggered (sender-row, receiver-ref) mute pairs into the
    K-slot-per-sender ref table (slot = ref % K). Returns (refs [k, n],
    ovf [n]) where ovf marks senders where two *distinct* refs collided
    in one slot this tick (≙ a mutemap set outgrowing its fixed width)."""
    big = jnp.int32(2**31 - 1)
    slot = jnp.where(trig, refs % k, 0)
    row = jnp.where(trig, mute_row, n)
    rmax = jnp.full((k, n), -1, jnp.int32).at[slot, row].max(
        jnp.where(trig, refs, -1), mode="drop")
    rmin = jnp.full((k, n), big, jnp.int32).at[slot, row].min(
        jnp.where(trig, refs, big), mode="drop")
    ovf = jnp.any((rmax >= 0) & (rmin != rmax), axis=0)
    return rmax, ovf


def empty_mute_slots(n: int, k: int):
    return jnp.full((k, n), -1, jnp.int32), jnp.zeros((n,), jnp.bool_)


# The short length of a delivery list is one PREFIX_SHARE-th of it
# (prefix_len): the tick's live entries, which the plan's sort packs to
# the front, fit it in every world whose list is mostly room.
PREFIX_SHARE = 4


def prefix_len(e: int) -> int:
    """The short one of the two static lengths delivery runs at after
    the plan's sort (module docstring, 2b), for a list of `e` entries:
    a quarter of it, in whole 128-lane tiles. Not below `e` for a list
    of a few tiles, which then has the one length."""
    return min(e, -(-e // (PREFIX_SHARE * 128)) * 128)


def rebuild_tables(tables, wds, tail, acc, seg_start):
    """Write this tick's accepted messages into the ring tables.

    `tables` = [(table [cap, rows, nn], s0, s1, r0, r1)]: the table of
    actors [s0, s1) takes word rows [r0, r1) of the sorted entries `wds`
    — every cohort's mailbox at its own width AND its own depth (`cap`
    is the table's: program.Cohort.mailbox_cap) and, with causal tracing
    on, its trace side lanes through the SAME (mask, source) pairs, so
    context and message cannot land in different slots. Arrival rank r
    of actor i (r < acc[i]) is entry seg_start[i] + r and lands in ring
    slot (tail[i] + r) % cap. Returns (new tables, indices gathered:
    the block's ranks that some row holds x rows a full-width block, B
    ranks x M a compacted one, summed over the blocks each cohort ran;
    None for rings of one block, whose count the caller knows)."""
    caps = {table.shape[0] for table, *_ in tables}
    e = wds.shape[1]

    def rank_of_slot(cap, tails):           # [cap, rows] rank of a slot
        return (jnp.arange(cap, dtype=jnp.int32)[:, None]
                - tails[None, :]) % cap

    # one depth for the world: the ranks once, every cohort a slice of
    # them (the window every program had before a cohort could state
    # its own); else a cohort's ranks at its own depth
    c = caps.pop() if len(caps) == 1 else None
    rels = None if c is None else rank_of_slot(c, tail)

    if c is not None and c <= REBUILD_BLOCK:
        # One block covers the ring: plane c (ring slot c of every
        # actor) pulls sorted entry seg_start + (c - tail) % cap, all
        # planes' indices in ONE gather per table. No depth, no loop.
        wmasks = rels < acc[None, :]
        srcs = jnp.minimum(seg_start[None, :] + rels, e - 1)
        out = []
        for table, s0, s1, r0, r1 in tables:
            nn = s1 - s0
            pulled = jnp.take(wds[r0:r1],
                              srcs[:, s0:s1].reshape(c * nn),
                              axis=1).reshape(r1 - r0, c, nn)
            out.append(jnp.where(wmasks[:, None, s0:s1],
                                 pulled.transpose(1, 0, 2), table))
        return out, None

    # A cohort's tables (its mailbox and, traced, its side lanes) share
    # their rows, so they share a loop and its depth; no two cohorts do.
    cohorts = {}
    for i, (_t, s0, s1, _r0, _r1) in enumerate(tables):
        cohorts.setdefault((s0, s1), []).append(i)
    out = [None] * len(tables)
    slots = jnp.int32(0)
    for (s0, s1), members in cohorts.items():
        tabs, gathered = _rebuild_cohort(
            [tables[i][0] for i in members],
            [tables[i][3:] for i in members], wds,
            rels[:, s0:s1] if rels is not None else rank_of_slot(
                tables[members[0]][0].shape[0], tail[s0:s1]),
            acc[s0:s1], seg_start[s0:s1])
        for i, tab in zip(members, tabs):
            out[i] = tab
        slots = slots + gathered
    return out, slots


def _rebuild_cohort(tabs, word_rows, wds, rels, acc, seg_start):
    """rebuild_tables' loops for one cohort: its tables `tabs`, each
    taking word rows `word_rows[i]` = (r0, r1) of `wds`; `rels`, `acc`
    and `seg_start` are the cohort's rows only. Runs ceil(max(acc) /
    REBUILD_BLOCK) blocks — none for a cohort that received nothing.
    Block k has work only for the rows with acc > k*B; their count falls
    with k, so the blocks come as two loops in turn: full-width ones
    while more than M = ceil(rows / B) rows are that deep — each a loop
    over the ranks the fullest row holds of it, one gather of the rows
    a rank —, then compacted ones, which pull for those rows alone.
    Returns (new tables, indices gathered)."""
    b = REBUILD_BLOCK
    e = wds.shape[1]
    nn = acc.shape[0]
    m = -(-nn // b)
    depth = jnp.max(acc)

    def place(k, tabs, pulled):
        """Rank k*B + j of every row, `pulled[i]` = [words, B, nn], into
        ring slot (tail + rank) % cap where rank < acc: a select chain,
        not a gather over the slot axis (module docstring, 4c)."""
        for j in range(b):
            here = ((rels == k * b + j) & (k * b + j < acc))[:, None, :]
            tabs = [jnp.where(here, pull[:, j][None], tab)
                    for tab, pull in zip(tabs, pulled)]
        return tuple(tabs)

    def full(carry):
        k, tabs = carry
        # Absolute, like every scope (state.py): a loop's body and its
        # test are computations of their own and would carry no phase.
        with phase_scope("delivery/rebuild"):
            # The ranks this block has at all: a rank no row holds is
            # not fetched (a gather is paid by the index).
            r = jnp.minimum(depth - k * b, b)
            # Each table's word rows of the list, cut once a block (a
            # cohort narrower than the list's records would copy its
            # rows of the whole list a rank otherwise); a single row as
            # the vector the gather reads it as.
            parts = [wds[r0] if r1 - r0 == 1 else wds[r0:r1]
                     for r0, r1 in word_rows]

            def rank(carry):
                j, pulled = carry
                with phase_scope("delivery/rebuild"):
                    # Rank k*B + j of every row: ONE gather of `nn`
                    # indices a table, written as plane j. The indices
                    # are in [0, e) as they stand, and the gather is
                    # told so: no mask of the out-of-range to compute,
                    # copy and select by, a rank.
                    srcs = jnp.minimum(seg_start + (k * b + j), e - 1)
                    return j + 1, tuple(
                        lax.dynamic_update_slice(
                            pull, jnp.take(part, srcs, axis=part.ndim - 1,
                                           mode="clip").reshape(-1, 1, nn),
                            (0, j, 0))
                        for pull, part in zip(pulled, parts))

            def more_ranks(carry):
                with phase_scope("delivery/rebuild"):
                    return carry[0] < r

            _, pulled = lax.while_loop(
                more_ranks, rank,
                (jnp.int32(0), tuple(jnp.zeros((r1 - r0, b, nn), jnp.int32)
                                     for r0, r1 in word_rows)))
            return k + 1, place(k, tabs, pulled)

    def compact(carry):
        k, tabs = carry
        with phase_scope("delivery/rebuild/compact"):
            ranks = k * b + jnp.arange(b, dtype=jnp.int32)        # [B]
            # The rows with a message in this block, at most M of them,
            # ascending, each with its segment's start: one sort. A row
            # that is not deep sorts behind them under a key past nn, so
            # the keys stay unique and the scatter below drops it.
            iota = jnp.arange(nn, dtype=jnp.int32)
            rows, seg_deep = lax.sort(
                (jnp.where(acc > k * b, iota, nn + iota), seg_start),
                num_keys=1)
            srcs = jnp.minimum(seg_deep[None, :m] + ranks[:, None],
                               e - 1).reshape(b * m)
            # B*M indices into the list, then the M windows of words x B
            # back to their rows' lanes for all the cohort's tables in
            # ONE scatter, sorted and unique and told so (XLA's TPU
            # scatter is one update after another otherwise). A row that
            # is not deep reads zeros and places none of them: no rank
            # of this block is below its acc.
            small = jnp.concatenate(
                [jnp.take(wds[r0:r1], srcs, axis=1).reshape((r1 - r0) * b, m)
                 for r0, r1 in word_rows])
            wide = jnp.zeros((small.shape[0], nn), jnp.int32).at[
                :, rows[:m]].set(small, mode="drop", indices_are_sorted=True,
                                 unique_indices=True)
            pulled = [part.reshape(-1, b, nn) for part in jnp.split(
                wide, list(accumulate((r1 - r0) * b
                                      for r0, r1 in word_rows))[:-1])]
            return k + 1, place(k, tabs, pulled)

    def more(k):
        return k * b < depth

    def wide_block(carry):
        with phase_scope("delivery/rebuild"):
            k = carry[0]
            return more(k) & (jnp.sum(acc > k * b) > m)

    n_full, tabs = lax.while_loop(wide_block, full,
                                  (jnp.int32(0), tuple(tabs)))
    blocks, tabs = lax.while_loop(lambda carry: more(carry[0]), compact,
                                  (n_full, tabs))
    # Full blocks are B ranks wide but the last, which ends at `depth`.
    return tabs, (jnp.minimum(depth, n_full * b) * nn
                  + (blocks - n_full) * m * b)


def deliver(buf, head, tail, alive, entries: Entries, *, n_local: int,
            mailbox_cap, spill_cap: int, overload_occ,
            shard_base, cohort_layout, mute_slots: int = 4, level=None,
            n_levels: int = 1, plan=None, pressured=None,
            cosort: bool = False, trace_buf=None) -> DeliveryResult:
    """`buf` is the per-cohort mailbox dict {type: [cap_c, 1+W_c, rows_c]};
    `cohort_layout` = [(type, s0, s1, w1_c)] tiles the local row space
    [0, n_local) in cohort order — bookkeeping (tails, segments, spill)
    stays global over rows, only the table rebuild is per cohort at its
    own width (≙ per-type pony_msg_t sizes, genfun.c) and depth.
    `mailbox_cap` and `overload_occ` are the rows': an int where every
    cohort has the same, else an [n_local] vector (state.rows_of).

    `level` ([E] int32, 0 = most urgent) folds the fork's actor
    *priorities* (actor.h priority hint; scheduler.c:1053-1078 priority
    inject) into the one sort: the composite key (target, level, arrival)
    keeps per-target segments contiguous while ordering contenders by
    priority — when a mailbox can't take everything this tick, higher
    priority wins the slots and lower priority spills. Level 0 is
    reserved for receiver-spill entries (FIFO: older must land first),
    level 1 for host injections.

    `trace_buf` (causal tracing on only): the per-cohort (trace_id,
    parent_span) side-lane tables; `words` then carries TWO extra
    trailing rows (the in-flight context) that both formulations move
    with the payload — the plan path through the cached permutation,
    the cosort path inside the one multi-operand sort — and the
    per-cohort rebuild writes `trace_buf` with the same masks/sources
    as `buf`. Spilled entries keep their trailing context rows (the
    spill tables are trace-width, state.init_state)."""
    n, c = n_local, mailbox_cap
    tgt, sender, words = entries
    e = tgt.shape[0]

    # Liveness is the target ROW's, so no entry asks for it: a send to
    # a dead slot sorts into that row's segment like any other and is
    # taken out where the per-row counts are (`cnt_live` below).
    in_range = (tgt >= 0) & (tgt < n)

    if level is None:
        level = jnp.zeros((e,), jnp.int32)
        n_levels = 1
    key = jnp.where(in_range, tgt * n_levels + level,
                    n * n_levels).astype(jnp.int32)

    # --- the delivery plan: stable-sort permutation + per-target segment
    # bounds (a merge of the target boundaries into the sorted keys
    # replaces the scatter-add histogram — see module docstring, point 3;
    # queries at target boundaries of the composite key span all priority
    # levels).
    #
    # Topology-stable traffic (every sustained benchmark's steady state:
    # ubench's in-flight cycle, fan-in's hot edges) produces the *same*
    # key vector tick after tick — the same actors firing along the same
    # refs at the same priorities. The plan is therefore cached in the
    # runtime state and revalidated with one cheap vector compare; the
    # O(E log² E) sort re-runs under `lax.cond` only when traffic
    # actually changes shape. ≙ the reference's O(1) pointer-based
    # messageq push (messageq.c:102-160): its "plan" is the receiver
    # pointer each sender holds; ours is the sort amortised across ticks.
    def _bounds(sorted_key):
        """Per-target segment bounds over an already-sorted key vector
        (shared by both delivery formulations so the key/level encoding
        lives once). In a scope of its own below the plan's: the plan's
        sort stays the one sort directly under `delivery/plan`, which
        is how a trace counts the misses."""
        with phase_scope("delivery/plan/bounds"):
            return segment_bounds(sorted_key, n, n_levels)

    # rebuild_tables' static guard: one depth for the world, one block
    one_block = isinstance(c, int) and c <= REBUILD_BLOCK

    # --- after the sort, the live entries are a prefix (module
    # docstring, 2b): everything below the sort is held at two static
    # lengths, the list's and `short`, and the tick's own count of live
    # entries chooses. Not for rings of one block (their window is
    # counted in operations) nor in cosort (no permutation to cut).
    short = e if one_block or cosort else prefix_len(e)
    n_live = jnp.sum(in_range.astype(jnp.int32)) if short < e else None

    def _compute_plan(k):
        sorted_key, p_ = stable_sort_with_keys(k)
        if short == e:
            return p_, _bounds(sorted_key)
        # every key past the live ones equals the largest query: the
        # same n + 1 numbers from a merge of n + 1 + short keys
        return p_, lax.cond(n_live <= short,
                            lambda _: _bounds(sorted_key[:short]),
                            lambda _: _bounds(sorted_key), operand=None)

    w1 = words.shape[0]
    if cosort:
        # Alternative formulation (opts.delivery == "cosort"): ONE stable
        # multi-operand sort carries the payload words WITH the key — no
        # cached plan, no permutation gathers afterwards. On hardware
        # where arbitrary lane gathers lower poorly this trades the
        # (plan-cached sort skip + two gathers) for a single native sort
        # per tick. Same FIFO guarantee: lax.sort is_stable preserves
        # arrival order within a (target, level) segment. The sort runs
        # inside the with_msgs cond below (idle ticks stay free); the
        # returned plan fields are placeholders cosort never reads.
        perm = jnp.arange(e, dtype=jnp.int32)
        bounds = jnp.zeros((n + 1,), jnp.int32)
    elif plan is None:
        with phase_scope("delivery/plan"):
            perm, bounds = _compute_plan(key)
    else:
        plan_key, plan_perm, plan_bounds = plan
        with phase_scope("delivery/plan"):
            perm, bounds = lax.cond(
                jnp.all(key == plan_key),
                lambda _: (plan_perm, plan_bounds),
                lambda _: _compute_plan(key),
                operand=None)

    def _empty_spill():
        refs, ovf = empty_mute_slots(n, mute_slots)
        return (Entries(tgt=jnp.full((spill_cap,), -1, jnp.int32),
                        sender=jnp.full((spill_cap,), -1, jnp.int32),
                        words=jnp.zeros((w1, spill_cap), jnp.int32)),
                jnp.zeros((n,), jnp.bool_), refs, ovf)

    # Everything below only matters when at least one message exists this
    # tick, so it all sits under one cond: an *idle* world's step touches
    # no mailbox memory at all (≙ the fork's idle-cost fix is the reason
    # it exists, README.md:8-10 — a waiting scheduler must cost ~nothing).
    def with_msgs(buf, trace_buf, wds, _):
        """The tick's delivery into the tables `buf` / `trace_buf`: over
        the whole sorted list, or over its first `ln` entries where the
        caller hands in their words, `wds` [w1, ln] sorted (the live
        entries fit them)."""
        ln = e if wds is None else wds.shape[1]
        if cosort:
            with phase_scope("delivery/plan"):
                ops = lax.sort((key, tgt, sender) + tuple(words),
                               num_keys=1, is_stable=True)
                key_s, tgt_s, snd_s = ops[0], ops[1], ops[2]
                seg_bounds = _bounds(key_s)
            with phase_scope("delivery/permute"):
                wds = jnp.stack(ops[3:])
                kt = jnp.where(key_s < n * n_levels, tgt_s,
                               n).astype(jnp.int32)
        else:
            snd_s = None
            seg_bounds = bounds
            head_perm = perm if ln == e else perm[:ln]
            with phase_scope("delivery/permute"):
                kt = jnp.where(in_range, tgt,
                               n).astype(jnp.int32)[head_perm]
                if wds is None:
                    wds = words[:, head_perm]        # [w1, ln] sorted
        ktc = jnp.minimum(kt, n - 1)
        seg_start = seg_bounds[:-1]              # [n]
        cnt = seg_bounds[1:] - seg_start         # [n] sends per target
        # Sends to dead slots drop with a counter (the reference's type
        # system makes this unrepresentable — ORCA keeps receivers alive).
        cnt_live = jnp.where(alive, cnt, 0)
        n_deadletter = jnp.sum(cnt - cnt_live)
        occ = tail - head
        space = jnp.maximum(c - occ, 0)
        acc = jnp.minimum(cnt_live, space)       # accepted per target
        new_tail = tail + acc

        # The ring rebuild, by arrival rank (rebuild_tables). Per
        # COHORT, at the cohort's own word width and only as deep as
        # the cohort's own fullest mailbox of this tick: a narrow
        # type's rebuild never moves the widest type's words (the HBM
        # win of per-cohort widths), a shallow type's never gathers the
        # blocks a deep one asked for.
        with phase_scope("delivery/rebuild"):
            tables = [(buf[cname], s0, s1, 0, w1c)
                      for cname, s0, s1, w1c in cohort_layout]
            if trace_buf is not None:
                # Trace side lanes (causal tracing on): the trailing two
                # word rows, through the same call as the payload.
                w1f = wds.shape[0]
                tables += [(trace_buf[cname], s0, s1, w1f - 2, w1f)
                           for cname, s0, s1, _w1c in cohort_layout]
            rebuilt, slots = rebuild_tables(tables, wds, tail, acc,
                                            seg_start)
            names = [cname for cname, *_ in cohort_layout]
            buf2 = dict(zip(names, rebuilt))
            tbuf2 = dict(zip(names, rebuilt[len(names):]))

        n_delivered = jnp.sum(acc)
        nrej = jnp.sum(cnt_live - acc)
        occ_after = new_tail - head

        # --- pressure paths, traced under a nested cond so the quiet
        # busy state pays nothing (≙ mute bookkeeping only on overload).
        def pressure(_):
            # What the protocol asks of a ROW is decided over the n rows
            # and read once per index vector (a gather is paid per
            # index, whatever it fetches). A target answers two things:
            # `bound`, the sorted position where its rejected suffix
            # begins (seg_start + accepted: entry i is rejected iff
            # i >= bound — no rank, no second table), and `hot_t`: over
            # the overload threshold after this tick, or declared
            # pressure. An entry to a dead row rides in that row's
            # segment and must be neither spilled nor a mute trigger:
            # liveness is folded into both answers (a dead row "accepts"
            # all of its segment, is never hot, never pressured); the
            # sender's bit sees its tables as they are.
            with phase_scope("delivery/pressure/spill"):
                bound = seg_start + jnp.where(alive, acc, cnt)
                hot_t = jnp.where(alive, occ_after, 0) > overload_occ
                if pressured is not None:
                    hot_t = hot_t | (pressured & alive)
                ok = kt < n
                rej = ok & (jnp.arange(ln, dtype=jnp.int32) >= bound[ktc])
                perm2, vspill, _ = compact_mask(rej, spill_cap)
                snd = snd_s if cosort else sender[head_perm]
                spill = Entries(
                    tgt=jnp.where(vspill, kt[perm2], -1),
                    sender=jnp.where(vspill, snd[perm2], -1),
                    words=jnp.where(vspill[None, :], wds[:, perm2], 0),
                )
            # Mute triggers (≙ actor.c:898-921 + mute rules
            # actor.c:1171-1235): a valid send whose receiver rejected it,
            # is now over the overload threshold, or has DECLARED pressure
            # (pony_apply_backpressure, actor.c:1137-1162) mutes the
            # sender — unless the sender is itself overloaded or itself
            # declared pressure (the reference's !OVERLOADED /
            # UNDER_PRESSURE guard, which prevents mute deadlocks among
            # hot actors): one bit a row, read once by the sender index.
            # Only senders resident on this shard can be muted here: on
            # a mesh a sender on another shard mutes at routing, one
            # tick later, by the hot word `mute.world` gathers from what
            # this tick leaves (occupancy over the line, anything
            # spilled: route._route_spill), and both are released by
            # the same bit of the same tick (mute.unmute_pass).
            with phase_scope("delivery/pressure/mute"):
                recv_hot = hot_t[ktc]
                hot_s = occ_after > overload_occ
                if pressured is not None:
                    hot_s = hot_s | pressured
                lsnd = snd - shard_base
                sender_local = (lsnd >= 0) & (lsnd < n)
                sc = jnp.minimum(jnp.maximum(lsnd, 0), n - 1)
                trig = ok & sender_local & (rej | recv_hot) & ~hot_s[sc]
                mute_row = jnp.where(trig, sc, n)
                refs, ovf = mute_ref_slots(trig, mute_row, kt + shard_base,
                                           n=n, k=mute_slots)
                # Every trigger wrote its ref (>= 0) into slot ref % K of
                # its sender's column: the table says who was muted.
                newly_muted = jnp.any(refs >= 0, axis=0)
            return spill, newly_muted, refs, ovf

        with phase_scope("delivery/pressure"):
            any_pressure = (nrej > 0) | jnp.any(occ_after > overload_occ)
            if pressured is not None:
                # Only when a send actually TARGETS a pressured receiver —
                # an unrelated actor's long-lived pressure (a stalled socket)
                # must not make every tick pay the pressure branch. Asked
                # of the rows, which have their counts already.
                any_pressure = any_pressure | jnp.any(
                    pressured & (cnt_live > 0))
            spill, newly_muted, new_refs, new_ovf = lax.cond(
                any_pressure, pressure, lambda _: _empty_spill(), operand=None)
        return (buf2, tbuf2, new_tail, spill, newly_muted, new_refs,
                new_ovf, n_delivered, nrej, n_deadletter) + (
                    () if one_block else (slots,))

    def no_msgs(_):
        spill, newly_muted, new_refs, new_ovf = _empty_spill()
        return (buf, dict(trace_buf) if trace_buf is not None else {},
                tail, spill, newly_muted, new_refs, new_ovf,
                jnp.int32(0), jnp.int32(0), jnp.int32(0)) + (
                    () if one_block else (jnp.int32(0),))

    # A tick whose only sends go to dead rows has messages too: it
    # counts them in with_msgs and delivers nothing.
    if short == e:
        any_valid = jnp.any(in_range)
        out = lax.cond(any_valid, partial(with_msgs, buf, trace_buf, None),
                       no_msgs, operand=None)
        took_prefix = None if one_block else jnp.int32(0)
    else:
        # Two conditionals in turn, each with ONE branch that rebuilds
        # the tables, not one conditional of three: XLA orders a
        # conditional's branches, and every branch but the last that
        # writes an operand in place copies it first — the whole
        # mailbox table once a rebuild block (8 ms at 1M rows x 64
        # slots). And the list's words are read by the FIRST alone, so
        # that they die where they did before the tick had two lengths:
        # the compiler keeps them in fast memory for `words[:, perm]`
        # only if nothing after it reads them (cycle's permute 43.6 ms,
        # 72.1 without). So the first conditional runs the whole list's
        # tick, or gathers the words of the prefix's, or idles; the
        # second runs the prefix's tick over the first's tables and
        # those words, or hands everything through.
        any_valid = n_live > 0
        fits = n_live <= short
        took_prefix = (any_valid & fits).astype(jnp.int32)

        def prefix_words(_):
            with phase_scope("delivery/permute"):
                return no_msgs(None), words[:, perm[:short]]

        no_words = jnp.zeros((w1, short), jnp.int32)
        out, wds_short = lax.switch(
            took_prefix + 2 * (~fits).astype(jnp.int32),
            (lambda _: (no_msgs(None), no_words), prefix_words,
             lambda _: (with_msgs(buf, trace_buf, None, None), no_words)),
            None)
        out = lax.cond(
            took_prefix > 0, lambda _: with_msgs(
                out[0], None if trace_buf is None else out[1], wds_short,
                None),
            lambda _: out, operand=None)
    (buf_out, tbuf_out, new_tail, spill, newly_muted, new_refs, new_ovf,
     n_delivered, nrej, n_deadletter, *slots) = out
    # A ring of one block carries no count out of the cond (its window
    # stays the program it was): it gathered its whole ring iff the
    # tick had a message.
    rebuild_slots = (any_valid.astype(jnp.int32) * (c * n) if one_block
                     else slots[0])

    return DeliveryResult(
        buf=buf_out, trace_buf=tbuf_out, tail=new_tail,
        spill=spill, spill_count=jnp.minimum(nrej, spill_cap),
        spill_overflow=nrej > spill_cap,
        newly_muted=newly_muted, new_mute_refs=new_refs,
        new_mute_ovf=new_ovf,
        n_delivered=n_delivered,
        n_rejected=nrej,
        n_deadletter=n_deadletter,
        plan_key=key, plan_perm=perm, plan_bounds=bounds,
        rebuild_slots=rebuild_slots, n_prefix=took_prefix,
    )
