"""Steps 3 and 4 of `engine.tick`: the outboxes routed (a mesh: the
communication backend the single-process reference never needed,
SURVEY.md §2.4) or passed through (one chip), listed and delivered.
How long the delivery list is, and who builds it, is decided here and
nowhere else: `list_sizes` derives the lengths from `state.layout_sizes`,
`deliver_routed` chooses the short list or the long by what arrived.
"""

from __future__ import annotations

from collections import namedtuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import pack
from ..ops.segment import compact_mask, stable_sort_carrying
from .delivery import (Entries, deliver, empty_mute_slots, mute_ref_slots,
                       prefix_len)
from .state import (ROUTE_COUNTERS, PhaseCursor, RtState, TickStatic,
                    layout_sizes, phase_scope, pool_index, rows_of)


def _route_pack(tgt, sender, words, *, shards: int, n_local: int,
                bucket: int):
    """One shard's entries `[route spill, outbox]` → its all-to-all
    buckets, with no read by index:

      sorted   (dt, ts, ss, ws): destination shard, target, sender and
               the `[w1, e]` words in ONE stable sort by destination
               (`dt` = `shards` for the invalid tail), so equal
               destinations keep their order: FIFO
      segments (seg_start, cnt, acc), `[shards]`: where a destination's
               run starts in the sorted entries, how long it is, and
               how much of it fits the bucket
      buckets  (bt, bs, bw, fill_f), `[shards * bucket]` / `[w1, ...]`:
               block d holds entries `seg_start[d] + j`, `j < acc[d]`,
               then -1 / -1 / 0: a contiguous slice of the sorted
               entries, masked
    """
    with phase_scope("route/sort"):
        valid = tgt >= 0
        dest = jnp.where(valid, tgt // n_local, shards).astype(jnp.int32)
        dt, ts, ss, *rows = stable_sort_carrying(
            dest, tgt, sender, *(words[i] for i in range(words.shape[0])))
        ws = jnp.stack(rows)                         # [w1, E] planar
    with phase_scope("route/bucket"):
        # Per-destination segment bounds via binary search; a
        # destination's block is then `bucket` consecutive sorted
        # entries from its segment's start. `dynamic_slice` clamps its
        # start so that the slice fits, so the sorted entries are padded
        # by a bucket: entry `seg_start[d] + j` stays at slot j. What
        # lies past `acc[d]` (the next segments, the pad) is masked.
        bounds = jnp.searchsorted(
            dt, jnp.arange(shards + 1, dtype=jnp.int32),
            side="left").astype(jnp.int32)
        seg_start = bounds[:-1]
        cnt = bounds[1:] - seg_start                 # [shards]
        acc = jnp.minimum(cnt, bucket)
        j = jnp.arange(bucket, dtype=jnp.int32)
        fill = j[None, :] < acc[:, None]             # [shards, bucket]
        fill_f = fill.reshape(shards * bucket)

        def blocks(x, empty):
            xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, bucket)])
            return jnp.concatenate([
                jnp.where(fill[d], lax.dynamic_slice_in_dim(
                    xp, seg_start[d], bucket, axis=-1), empty)
                for d in range(shards)], axis=-1)
        bt, bs, bw = blocks(ts, -1), blocks(ss, -1), blocks(ws, 0)
    return (dt, ts, ss, ws), (seg_start, cnt, acc), (bt, bs, bw, fill_f)


def _unpack_fits(shards: int, bucket: int, l_in: int) -> bool:
    """Whether a meshed shard's window holds the SHORT delivery list
    beside the long one (`list_sizes`): only where the received buckets
    are longer than what `_route_unpack` joins them into. One chip and
    a small explicit `route_bucket` keep the one list they had. Static;
    the seam the tests patch to get the long list alone."""
    return shards > 1 and shards * bucket > l_in


def _route_unpack(received: Entries, fill, *, shards: int, bucket: int,
                  l_in: int) -> Entries:
    """The received buckets joined front to front: `_route_pack` run
    backwards. Block d of `received` (`[shards * bucket]`, words
    `[w1, ...]`) holds `fill[d]` entries at its front, then -1 / -1 / 0,
    so writing the blocks in order, block d at `sum(fill[:d])` of a
    buffer of `l_in + bucket`, lets each block cover the pad of the one
    before it: `shards - 1` contiguous copies, no read by index, and
    block order is arrival order (FIFO). Returns the first `l_in`
    entries — all of them where `sum(fill) <= l_in`, which is the
    caller's to check (`deliver_routed`'s `fits`)."""
    with phase_scope("route/unpack"):
        start = jnp.cumsum(fill) - fill              # [shards]

        def join(x, empty):
            def block(d):
                return lax.slice_in_dim(x, d * bucket, (d + 1) * bucket,
                                        axis=-1)
            out = jnp.pad(block(0), [(0, 0)] * (x.ndim - 1) + [(0, l_in)],
                          constant_values=empty)
            for d in range(1, shards):
                out = lax.dynamic_update_slice_in_dim(
                    out, block(d), start[d], axis=-1)
            return lax.slice_in_dim(out, 0, l_in, axis=-1)
        return Entries(tgt=join(received.tgt, -1),
                       sender=join(received.sender, -1),
                       words=join(received.words, 0))


# The cached delivery plan keeps the LONG list's shape (RtState.plan_key
# / plan_perm, state.layout_sizes' n_delivery_entries) and belongs to
# one list length at a time. A tick over the short list compares and
# stores its key and permutation in the first `e_short` entries and
# marks the entry after them -1; no key is negative, so a long tick
# never validates what a short one stored, and a short tick asks the
# mark before it looks: the plan of one length never validates, and
# never permutes, the other's list.

def _short_plan(plan, e_short: int):
    """(key, perm, bounds) for `deliver` over the short list: the
    stored plan's front if a short tick stored it, else a key that
    matches nothing."""
    key, perm, bounds = plan
    mine = key[e_short] < 0
    return jnp.where(mine, key[:e_short], -1), perm[:e_short], bounds


def _store_short_plan(plan, key_s, perm_s):
    """The plan arrays after a tick over the short list."""
    key, perm, _bounds = plan
    marked = jnp.concatenate([key_s, jnp.full((1,), -1, key_s.dtype)])
    return (lax.dynamic_update_slice(key, marked, (0,)),
            lax.dynamic_update_slice(perm, perm_s, (0,)))


def _route(entries: Entries, *, shards: int, n_local: int, bucket: int,
           rspill_cap: int, overload_occ, head, tail, shard_base,
           mute_slots: int, hot_anywhere, hot_global,
           pressured_local, blob=None):
    """Mesh routing: pack entries into per-destination-shard buckets
    (`_route_pack`: one payload-carrying sort, then a contiguous slice a
    destination) and exchange them with three all_to_all over the actor
    axis (ICI): targets, senders, words.

    Returns (received Entries [shards*bucket], new route-spill, spill count,
    overflow flag, newly muted [n_local], their refs, ref overflow, the
    senders muted behind another shard's receiver, 1 where the spill read
    the sorted entries' prefix alone, blob results or None, (entries
    shipped, those of them off-shard)). Its parts carry the scopes
    `pony/route/sort`, `/bucket`, `/exchange` and `/spill` with
    `/spill/lookup` and `/spill/mute` below it (state.STEP_SCOPES), at
    either of the spill's two lengths (`_route_spill`: the sorted
    entries, or the quarter of them the valid ones fit, by the tick's
    own count).
    Bucket overflow keeps messages on the source shard (route-spill,
    retried first next step) and mutes the sender — the occupancy signal
    there is "the link to that shard is saturated"; a sender whose
    message goes to a receiver that is overloaded or declares pressure,
    on whatever shard, mutes by the mesh-wide hot word (`_route_spill`:
    ≙ ponyint_maybe_mute reading the receiver's flags at the send).
    `head` / `tail` are the rows' at the tick's START, for the senders'
    exemption.

    Blob MIGRATION (`blob` = dict(data, used, len, gen, bbase, bsl,
    shard, mask) when the program routes Blob args on a mesh): a blob
    rides its message across the ICI — per blob-arg word position, a
    length row + the payload words concatenate onto the exchanged
    words; the source shard frees the shipped slot, the receiving shard
    allocates a fresh local slot (new generation) and rewrites the
    handle word before delivery. Same-shard bucket blocks skip
    migration (the handle is already dereferenceable). A receive-side
    pool-full drop delivers the message with a null handle and counts
    in n_blob_remote — backpressure-safe data loss made visible, never
    corruption. Route-spilled entries keep their (still-local) blobs
    and migrate when the retry actually ships. ≙ nothing in the
    reference — libponyrt is single-node; this is the distributed half
    of pony_alloc_msg payload movement.
    """
    tgt, sender, words = entries
    ((dt, ts, ss, ws), (seg_start, cnt, acc),
     (bt, bs, bw, fill_f)) = _route_pack(
        tgt, sender, words, shards=shards, n_local=n_local, bucket=bucket)
    with phase_scope("route/bucket"):
        # What ships this tick, and how much of it leaves the shard
        # (RtState.route_counts): read off the [shards] bucket fills.
        n_routed = jnp.sum(acc)
        n_remote = n_routed - jnp.take(acc, shard_base // n_local)

    blob_out = None
    if blob is not None:
        # --- migration, source side: for every blob-carrying bucketed
        # entry bound OFF-shard, append (len, payload...) rows and free
        # the local slot. Positions are static (the Blob-arg mask).
        bdata, bused, blen, bgen = (blob["data"], blob["used"],
                                    blob["len"], blob["gen"])
        bbase, bsl = blob["bbase"], blob["bsl"]
        mask_np = blob["mask"]                   # STATIC numpy masks
        mask = jnp.asarray(mask_np)
        mask_iso = jnp.asarray(blob["mask_iso"])
        wb = bdata.shape[0] // bsl       # flat pool: state.pool_index
        word_i = jnp.arange(wb, dtype=jnp.int32)[:, None]

        def whole(slots, ok):
            """Flat indices of whole blobs, [wb, len(slots)]; one past
            the end (filled / dropped) where not `ok`."""
            return jnp.where(ok[None, :],
                             pool_index(bsl, word_i, slots[None, :]),
                             bdata.shape[0])
        n_gids = mask.shape[0]
        sb = shards * bucket
        gid = bw[0]
        g = jnp.clip(gid, 0, n_gids - 1)
        gid_ok = fill_f & (gid >= 0) & (gid < n_gids)
        # Off-shard only: bucket block s goes to shard s.
        off_shard = jnp.broadcast_to(
            (jnp.arange(shards, dtype=jnp.int32)[:, None]
             != blob["shard"]), (shards, bucket)).reshape(sb)
        extra_rows = []
        freed = jnp.zeros((bsl,), jnp.bool_)
        positions = [w for w in range(mask_np.shape[1])
                     if bool(mask_np[:, w].any())]
        for wpos in positions:
            h = bw[1 + wpos]
            hl = pack.blob_slot(h) - bbase
            hs = jnp.where((hl >= 0) & (hl < bsl), hl, bsl)
            okh = (gid_ok & off_shard & mask[g, wpos] & (h >= 0)
                   & (hs < bsl)
                   & (jnp.take(bgen, hs, mode="fill", fill_value=-1)
                      == pack.blob_gen_of(h))
                   & jnp.take(bused, hs, mode="fill", fill_value=False))
            hx = jnp.where(okh, hl, bsl)
            extra_rows.append(jnp.where(
                okh, jnp.take(blen, hx, mode="fill", fill_value=0),
                jnp.int32(-1))[None, :])             # -1 = no payload
            extra_rows.append(jnp.take(
                bdata, whole(hx, okh), mode="fill",
                fill_value=0))                       # [wb, sb]
            # Iso handles MOVE (source freed); val handles COPY — the
            # receiver gets a replica, other readers keep the original.
            freed = freed.at[jnp.where(okh & mask_iso[g, wpos],
                                       hl, bsl)].set(True, mode="drop")
        bused = bused & ~freed
        blen = jnp.where(freed, 0, blen)
        n_shipped = jnp.sum(freed.astype(jnp.int32))
        bw = jnp.concatenate([bw] + extra_rows, axis=0)

    with phase_scope("route/exchange"):
        rt = lax.all_to_all(bt, "actors", split_axis=0, concat_axis=0,
                            tiled=True)
        rs = lax.all_to_all(bs, "actors", split_axis=0, concat_axis=0,
                            tiled=True)
        rw = lax.all_to_all(bw, "actors", split_axis=1, concat_axis=1,
                            tiled=True)

    if blob is not None:
        # --- migration, receive side: allocate a local slot per arrived
        # payload (disjoint ranks over the compacted free list), write
        # len+words, bump the slot generation, rewrite the handle word.
        w1b = words.shape[0]
        rw_main = rw[:w1b]
        sb = shards * bucket
        n_pos = len(positions)
        permf, vfree, _ = compact_mask(~bused, bsl)
        free_slots = jnp.where(vfree, permf.astype(jnp.int32), -1)
        has_all = jnp.stack(
            [(rw[w1b + k * (1 + wb)] >= 0).astype(jnp.int32)
             for k in range(n_pos)])
        rank = (jnp.cumsum(has_all.reshape(-1)) - 1).reshape(n_pos, sb)
        n_dropped = jnp.int32(0)
        new_words = [rw_main[i] for i in range(w1b)]
        for k, wpos in enumerate(positions):
            base_row = w1b + k * (1 + wb)
            lenr = rw[base_row]
            has = lenr >= 0
            slot_l = jnp.take(free_slots, jnp.where(has, rank[k], bsl),
                              mode="fill", fill_value=-1)
            ok = has & (slot_l >= 0)
            n_dropped = n_dropped + jnp.sum(
                (has & ~ok).astype(jnp.int32))
            sx = jnp.where(ok, slot_l, bsl)
            newgen = (jnp.take(bgen, sx, mode="fill", fill_value=0)
                      + 1) & pack.BLOB_GEN_MASK
            bgen = bgen.at[sx].set(newgen, mode="drop")
            bused = bused.at[sx].set(True, mode="drop")
            blen = blen.at[sx].set(jnp.where(ok, lenr, 0), mode="drop")
            bdata = bdata.at[whole(sx, ok)].set(
                rw[base_row + 1:base_row + 1 + wb], mode="drop")
            newh = pack.blob_handle(bbase + slot_l, newgen)
            # has & ok → fresh local handle; has & ~ok → dropped (null);
            # ~has → original word untouched (not a blob for this gid,
            # or a same-shard handle that skipped migration).
            new_words[1 + wpos] = jnp.where(
                ok, newh, jnp.where(has, jnp.int32(-1),
                                    new_words[1 + wpos]))
        rw = jnp.stack(new_words)
        n_received = jnp.sum(has_all) - n_dropped
        blob_out = ((bdata, bused, blen, bgen),
                    n_shipped, n_received, n_dropped)

    with phase_scope("route/spill"):
        # The spill reads the sorted entries only behind this barrier:
        # without it the compiler fuses `maximum(ts, 0)` into the
        # bucket's slices and the lookup `hot_global[ts]` loses its fast
        # memory, 114 ms for 68 at 8.4M entries (PERF.md §6, PR 41).
        # `_route_spill` cuts its prefix from what comes out of the
        # barrier, never from the sort's own outputs, for the same
        # reason.
        ts, ss, ws, dt = lax.optimization_barrier((ts, ss, ws, dt))
        # The invalid tail is keyed `shards` and sorts last: the valid
        # entries are the first `n_live` of the sorted ones.
        n_live = seg_start[-1] + cnt[-1]
        spilled = _route_spill(
            ts, ss, ws, dt, seg_start, cnt - acc, n_live, shards=shards,
            n_local=n_local, bucket=bucket, rspill_cap=rspill_cap,
            overload_occ=overload_occ, head=head, tail=tail,
            shard_base=shard_base, mute_slots=mute_slots,
            hot_anywhere=hot_anywhere, hot_global=hot_global,
            pressured_local=pressured_local)
    received = Entries(tgt=rt, sender=rs, words=rw)
    return (received, *spilled, blob_out, (n_routed, n_remote))


def _route_spill(ts, ss, ws, dt, seg_start, over, n_live, *, shards: int,
                 n_local: int, bucket: int, rspill_cap: int, overload_occ,
                 head, tail, shard_base, mute_slots: int,
                 hot_anywhere, hot_global, pressured_local):
    """What did not fit its bucket, and who mutes for what it sent: the
    sorted entries (`ts`, `ss`, `ws` by destination `dt`), each
    destination's `seg_start` and overflow `over`, and how many of the
    entries are valid (`n_live`) → (new route-spill, spill count,
    overflow flag, newly muted [n_local], their refs, ref overflow, the
    senders muted behind a receiver on another shard, 1 where the tick
    read the entries' prefix alone).

    Backpressure across the mesh (≙ ponyint_maybe_mute, actor.c:898-921:
    the SENDER reads the receiver's flags on every send, whatever
    scheduler thread either runs on). A receiver's own shard mutes only
    the senders that live on it (delivery.deliver, step 5, at the end of
    the tick that rejected them or left the receiver over its overload
    line). Every other sender learns it here, from `hot_global`
    (mute.world): the word of every row of the mesh as THIS tick found
    it — declares pressure; overloaded: over its overload line, or
    messages parked for it in its shard's device spill — so a sender on
    another shard mutes in the first tick in which it sends to a
    receiver that the tick BEFORE left overloaded, one tick after the
    receiver's local senders, with the receiver as its muting ref. Its
    message of this tick still ships. It is released by `mute.
    unmute_pass` when that receiver has recovered. The bound that
    follows: a sender that sends one message a dispatch has at most TWO
    outside a mailbox (one chip: one) — the one the receiver rejected,
    and the one it sent in the tick that muted it; it then does not run
    until nothing is parked for that receiver, its own among them.
    Senders that are themselves overloaded or declare pressure never
    mute (the reference's exemption).

    The two lengths (PR 50; `delivery.deliver`'s since PR 45, here).
    Every read and scatter by index below is paid by the SLOT of the
    sorted entries, valid or not, and the slots are a shard's whole
    route spill and outbox for the fraction of them a tick routes. The
    sort keys the invalid tail `shards`, so the valid entries are the
    first `n_live` sorted ones and nothing below asks about the rest: a
    hot target, a parked entry and a muting sender are all valid
    entries. So the window holds the spill at two static lengths, the
    entries' `e` and `prefix_len(e)` (delivery's own quarter; static,
    and this module's name for it is the seam the tests patch to get
    the whole length alone), and `n_live` chooses: where the valid
    entries fit the prefix, the lookup, the overflow's compaction (an
    entry's rank in its segment is its position in the sorted entries,
    which a cut behind it does not move), the exemption and the ref
    table's scatters run over `ts[:L]`, `ss[:L]`, `ws[:, :L]`,
    `dt[:L]`; else over all of them.
    One algorithm, the same seven results bit for bit either way
    (tests/test_route_spill_prefix.py); `pr_t` and `rej` never leave
    the branch. The caller hands the entries over from BEHIND its
    `optimization_barrier` and the cut is made here, inside the branch:
    cut from the sort's own outputs it would fuse into the bucket's
    slices as `maximum(ts, 0)` did (PERF.md §6, PR 41). The eighth
    result feeds `n_route_prefix` (RtState.route_counts): 1 where the
    lookup or the pressure branch ran at the short length — a quiet
    tick runs neither at either length and counts 0."""
    e = ts.shape[0]
    nrej = jnp.sum(over)
    w1 = ws.shape[0]

    def empty_spill():
        return Entries(tgt=jnp.full((rspill_cap,), -1, jnp.int32),
                       sender=jnp.full((rspill_cap,), -1, jnp.int32),
                       words=jnp.zeros((w1, rspill_cap), jnp.int32))

    def quiet(_):
        refs, ovf = empty_mute_slots(n_local, mute_slots)
        return (empty_spill(), jnp.zeros((n_local,), jnp.bool_), refs, ovf,
                jnp.int32(0))

    def over_the_first(length: int):
        """`spilled` over the first `length` sorted entries, as a branch."""
        def run(_):
            # the scope again: it names the conditionals inside a branch
            with phase_scope("route/spill"):
                if length == e:
                    return spilled(ts, ss, ws, dt)
                return spilled(*(lax.slice_in_dim(x, 0, length, axis=-1)
                                 for x in (ts, ss, ws, dt)))
        return run

    def spilled(ts, ss, ws, dt):
        e = ts.shape[0]
        # A read by index is paid by the entry whatever it fetches, so the
        # lookup runs only on a tick whose world bit 0 or 3 (`hot_anywhere`,
        # mute.world) says the word can hold a set bit: where both are clear
        # `hot_global` IS zeros, and so is the answer.
        def looked_up(_):
            with phase_scope("route/spill/lookup"):
                hit = (ts >= 0) & (jnp.take(
                    hot_global, jnp.maximum(ts, 0), mode="clip") != 0)
                return hit, jnp.any(hit)

        pr_t, any_pr = lax.cond(
            hot_anywhere, looked_up,
            lambda _: (jnp.zeros((e,), jnp.bool_), jnp.bool_(False)),
            operand=None)

        def parked(_):
            # Bucket overflow → route spill (stays on this shard, ordered).
            # `dt` is sorted and `shards` is small: a select a destination
            # finds an entry's segment start, no read by index.
            start = jnp.zeros((e,), jnp.int32)
            for d in range(shards):
                start = jnp.where(dt == d, seg_start[d], start)
            rej = (dt < shards) & (jnp.arange(e, dtype=jnp.int32) - start
                                   >= bucket)
            perm2, vsp, _ = compact_mask(rej, rspill_cap)
            return rej, Entries(
                tgt=jnp.where(vsp, ts[perm2], -1),
                sender=jnp.where(vsp, ss[perm2], -1),
                words=jnp.where(vsp[None, :], ws[:, perm2], 0),
            )

        def pressure(_):
            # Mute the (always local) senders of parked messages and of
            # messages to a hot receiver. The compaction runs only where a
            # link overflowed: a tick that is here for a hot receiver alone
            # — every tick of a skewed world — parks nothing.
            rej, spill = lax.cond(
                nrej > 0, parked,
                lambda _: (jnp.zeros((e,), jnp.bool_), empty_spill()),
                operand=None)
            with phase_scope("route/spill/mute"):
                lsnd = ss - shard_base
                s_ok = (rej | pr_t) & (lsnd >= 0) & (lsnd < n_local)
                sc = jnp.minimum(jnp.maximum(lsnd, 0), n_local - 1)
                # ≙ the reference's !OVERLOADED/UNDER_PRESSURE sender
                # exemption (actor.c mute rules): a sender that is itself
                # hot or has itself declared pressure never mutes — prevents
                # two host-pressured actors that message each other from
                # mutually muting into a stall. One bit a row, decided over
                # the rows and read once by the sender index; `head` and
                # `tail` are the tick's START, as the hot word is: what the
                # tick before left, which is what its delivery exempted by.
                exempt = ((tail - head) > overload_occ) | pressured_local
                trig = s_ok & ~exempt[sc]
                mute_row = jnp.where(trig, sc, n_local)
                refs, ovf = mute_ref_slots(trig, mute_row, ts, n=n_local,
                                           k=mute_slots)
                # Every trigger wrote its ref (>= 0) into its sender's
                # column: the table says who was muted, and behind whom.
                newly_muted = jnp.any(refs >= 0, axis=0)
                elsewhere = (refs >= 0) & (refs // n_local
                                           != shard_base // n_local)
                n_remote = jnp.sum(
                    jnp.any(elsewhere, axis=0).astype(jnp.int32))
            return spill, newly_muted, refs, ovf, n_remote

        return lax.cond((nrej > 0) | any_pr, pressure, quiet, operand=None)

    short = prefix_len(e)
    if short < e:
        fits = n_live <= short
        new_rspill, newly_muted, new_refs, new_ovf, n_remote = lax.cond(
            fits, over_the_first(short), over_the_first(e), operand=None)
        took_prefix = fits & (hot_anywhere | (nrej > 0))
    else:       # a few tiles of entries: the one length
        new_rspill, newly_muted, new_refs, new_ovf, n_remote = \
            over_the_first(e)(None)
        took_prefix = jnp.bool_(False)
    return (new_rspill, jnp.minimum(nrej, rspill_cap), nrej > rspill_cap,
            newly_muted, new_refs, new_ovf, n_remote,
            took_prefix.astype(jnp.int32))


# The static lengths of a shard's lists: the per-destination all_to_all
# bucket (mesh only), the short list's routed part, the short list, and
# whether the window holds the short list beside the long.
ListSizes = namedtuple("ListSizes", "bucket l_in e_short short")


# What steps 3 and 4 leave: delivery.deliver's result; the new route
# spill, its count, whether it overflowed (fatal); the senders a link
# or a hot receiver muted, [nl], and their refs (nobody on one chip);
# {counter: this tick's count} for the leaves of RtState.route_counts
# (state.list_counters); spawn.Pool after migration and the blobs
# that arrived; the delivery list's targets, >= 0 where valid, for
# lanes.phase_cost_lanes (read at analysis >= 1 only).
Routed = namedtuple("Routed", "res rspill rspill_count rspill_over muted "
                    "mute_refs mute_ovf counts pool nb_moved listed_tgt")


def list_sizes(program, opts) -> ListSizes:
    e_out, bucket, _n_entries = layout_sizes(program, opts)
    # What one shard can emit a tick (its route spill and its outbox) is
    # what a balanced world hands it back: the length of the short
    # delivery list's routed part (step 4 of the tick).
    l_in = opts.spill_cap + e_out
    return ListSizes(bucket, l_in,
                     opts.spill_cap + opts.inject_slots + l_in,
                     _unpack_fits(program.shards, bucket, l_in))


def _local_rows(entries: Entries, base) -> Entries:
    """Global target ids -> this shard's rows."""
    return entries._replace(tgt=jnp.where(
        entries.tgt >= 0, entries.tgt - base, -1))


def _inject_local(k: TickStatic, base, inject_tgt):
    # Injections are replicated to all shards; each keeps the rows it owns.
    inj_l = inject_tgt - base
    return jnp.where((inj_l >= 0) & (inj_l < k.nl), inj_l, -1)


def delivery_list(k: TickStatic, st: RtState, base, inject_tgt,
                  inject_words, incoming: Entries, out_entries):
    """--- 4. delivery list: receiver spill first (oldest), then host
    injections, then routed messages: `incoming` (local rows) behind
    the receiver spill and the injections, and every entry's level."""
    nl = k.nl
    dev_cohorts = k.program.device_cohorts
    inj_local = _inject_local(k, base, inject_tgt)
    dspill_e = Entries(st.dspill_tgt, st.dspill_sender,
                       st.dspill_words)
    all_e = Entries(
        tgt=jnp.concatenate([dspill_e.tgt, inj_local,
                             incoming.tgt]),
        sender=jnp.concatenate([dspill_e.sender,
                                jnp.full_like(inj_local, -1),
                                incoming.sender]),
        words=jnp.concatenate([dspill_e.words, inject_words,
                               incoming.words], axis=1),
    )

    # The level of an incoming entry is its sender's cohort's: a
    # constant of the program when it has one priority, and on
    # one chip a constant of each segment of `incoming` (the
    # route spill, empty there, then one outbox a cohort). Only
    # a mesh with several priorities has to ask each entry for
    # its sender.
    if len(k.pri_rank) <= 1:
        lvl_in = jnp.full_like(incoming.tgt, 2)
    elif k.p == 1:
        lvl_in = jnp.concatenate(
            [jnp.full_like(st.rspill_tgt, 2)]
            + [jnp.full_like(o.tgt, 2 + k.pri_rank[ch.priority])
               for ch, o in zip(dev_cohorts, out_entries)])
    else:
        prio_row = np.zeros((nl,), np.int32)
        for ch in dev_cohorts:
            prio_row[ch.local_start:ch.local_stop] = \
                k.pri_rank[ch.priority]
        snd_in = incoming.sender
        srow = jnp.where(snd_in >= 0, snd_in, 0) % nl
        lvl_in = jnp.where(snd_in >= 0,
                           2 + jnp.asarray(prio_row)[srow],
                           jnp.int32(2)).astype(jnp.int32)
    lvl_all = jnp.concatenate([
        jnp.zeros_like(dspill_e.tgt),
        jnp.ones_like(inj_local),
        lvl_in])
    return all_e, lvl_all


def deliver_routed(k: TickStatic, st: RtState, w, inject_tgt, inject_words,
                   out_entries, cl, pool, phase: PhaseCursor) -> Routed:
    """Steps 3 and 4 over the tables as the claims left them (`cl`);
    opens the scopes `route` and `delivery` on `phase`."""
    p, nl, opts, base = k.p, k.nl, k.opts, w.base
    head, tail0, alive = cl.head, cl.tail0, cl.alive
    bucket, l_in, e_short, short_list = k.lists

    # --- 3. route (mesh) or pass through (single chip).
    phase("route")
    rspill_e = Entries(st.rspill_tgt, st.rspill_sender, st.rspill_words)
    out_cat = Entries(
        tgt=jnp.concatenate([rspill_e.tgt] +
                            [o.tgt for o in out_entries]),
        sender=jnp.concatenate([rspill_e.sender] +
                               [o.sender for o in out_entries]),
        words=jnp.concatenate([rspill_e.words] +
                              [o.words for o in out_entries], axis=1),
    )
    route_muted = jnp.zeros((nl,), jnp.bool_)
    route_refs, route_ovf = empty_mute_slots(nl, opts.mute_slots)
    routed = None
    nb_moved = jnp.int32(0)
    if p > 1:
        rblob = None
        if k.blob_route is not None:
            rblob = {**dict(zip(("data", "used", "len", "gen"), pool.cur)),
                     "bbase": pool.base, "bsl": opts.blob_slots,
                     "shard": w.shard, "mask": k.blob_route[0],
                     "mask_iso": k.blob_route[1]}
        (incoming, new_rspill, rsp_count, rsp_over, route_muted,
         route_refs, route_ovf, n_remote_mutes, n_route_prefix,
         route_blob_out, routed) = _route(
            out_cat, shards=p, n_local=nl, bucket=bucket,
            rspill_cap=k.s_cap,
            overload_occ=rows_of(k.program, "overload_occ"),
            head=st.head, tail=st.tail, shard_base=base,
            mute_slots=opts.mute_slots,
            hot_anywhere=w.hot_anywhere, hot_global=w.hot_global,
            pressured_local=st.pressured, blob=rblob)
        if route_blob_out is not None:
            cur, n_ship, n_recv, n_drop = route_blob_out
            pool = pool._replace(
                cur=cur, n_free=pool.n_free + n_ship,
                n_alloc=pool.n_alloc + n_recv,
                n_remote=pool.n_remote + n_drop)
            nb_moved = n_recv
        if not short_list:
            incoming = _local_rows(incoming, base)
    else:
        incoming = _local_rows(out_cat, base)
        new_rspill = rspill_e                  # unused, stays empty
        rsp_count = st.rspill_count[0]
        rsp_over = jnp.bool_(False)

    def delivered(all_e, lvl_all, plan):
        return deliver(st.buf, head, tail0, alive, all_e,
                       n_local=nl,
                       mailbox_cap=rows_of(k.program, "mailbox_cap"),
                       spill_cap=k.s_cap,
                       overload_occ=rows_of(k.program, "overload_occ"),
                       shard_base=base,
                       cohort_layout=k.cohort_layout,
                       mute_slots=opts.mute_slots,
                       level=lvl_all, n_levels=k.n_levels, plan=plan,
                       pressured=st.pressured,
                       cosort=(opts.delivery == "cosort"),
                       trace_buf=st.trace_buf if opts.tracing else None)

    plan = (st.plan_key, st.plan_perm, st.plan_bounds)
    n_unpacked = jnp.int32(0)
    listed_tgt = None
    if not short_list:
        all_e, lvl_all = delivery_list(k, st, base, inject_tgt,
                                       inject_words, incoming, out_entries)
        phase("delivery")
        res = delivered(all_e, lvl_all, plan)
        listed_tgt = all_e.tgt
    else:
        # A meshed shard delivers over what ARRIVED. The received
        # buckets are `p * bucket` entries whatever came (at the
        # default bucket four outboxes' worth for one outbox's worth
        # of messages), and every list phase of delivery is paid by
        # the entry. So the window holds delivery at two static
        # lengths and the tick's arrivals choose: where they fit one
        # shard's outbox (`l_in`: what a shard can emit is what a
        # balanced world hands it back) the buckets are joined front
        # to front (`_route_unpack`) and delivery runs over
        # `e_short` entries; a tick that does not fit — a skewed
        # one, a fan-in onto this shard — runs the list it always
        # ran. Same mailboxes, tails, spill and mutes either way:
        # delivery is stable in arrival order and sorts the invalid
        # last. `deliver` holds no collective, so each shard takes
        # its own branch.
        with phase_scope("route/unpack"):
            fill = jnp.sum(
                (incoming.tgt >= 0).reshape(p, bucket).astype(jnp.int32),
                axis=1)
            fits = jnp.sum(fill) <= l_in
        n_unpacked = fits.astype(jnp.int32)

        def over(incoming, plan):
            with phase_scope("route"):
                all_e, lvl_all = delivery_list(
                    k, st, base, inject_tgt, inject_words,
                    _local_rows(incoming, base), out_entries)
            with phase_scope("delivery"):
                return delivered(all_e, lvl_all, plan)

        def short(_):
            joined = _route_unpack(incoming, fill, shards=p,
                                   bucket=bucket, l_in=l_in)
            with phase_scope("delivery/plan"):
                cached = _short_plan(plan, e_short)
            res = over(joined, cached)
            with phase_scope("delivery/plan"):
                key, perm = _store_short_plan(plan, res.plan_key,
                                              res.plan_perm)
            return res._replace(plan_key=key, plan_perm=perm)

        phase("delivery")
        res = lax.cond(fits, short, lambda _: over(incoming, plan),
                       operand=None)
        if opts.analysis >= 1:
            # phase_cost_lanes counts the list's valid entries, the same
            # in either list: what lies before the routed part and what
            # arrived (on its target's shard: valid where its row is).
            listed_tgt = jnp.concatenate([
                st.dspill_tgt, _inject_local(k, base, inject_tgt),
                incoming.tgt])
    counts = {} if routed is None else dict(
        zip(ROUTE_COUNTERS, (*routed, n_unpacked,
                             w.hot_anywhere.astype(jnp.int32),
                             n_route_prefix, n_remote_mutes)))
    if res.n_prefix is not None:
        counts["n_prefix"] = res.n_prefix
    return Routed(res, new_rspill, rsp_count, rsp_over, route_muted,
                  route_refs, route_ovf, counts, pool, nb_moved, listed_tgt)
