"""The opt-in lanes of `engine.tick` (≙ the fork's --ponyanalysis
records, analysis.h:16-31, analysis.c): the profiler lanes, the phase
tallies, the causal-trace spans, the event ring (step 5b), the vote's
aggregates. Every gate is PYTHON-level: at level 0 none of this is in the
jaxpr; the tests trap these functions to prove it, so the tick calls them
through this module.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ..config import RuntimeOptions
from ..ops.segment import compact_mask
from ..program import Program
from .state import PHASE_NAMES, QW_BUCKETS, RtState, TickStatic, rows_of


def _qwait_bucket(delta):
    """Power-of-two bucket index of a queue-wait delta (in ticks):
    bucket k ↔ [2^k, 2^(k+1)) with deltas clipped to >= 1 and the last
    bucket open-ended — floor(log2) spelled as QW_BUCKETS-1 vector
    compares, which XLA fuses into the surrounding reductions."""
    d = jnp.maximum(delta, 1)
    b = jnp.zeros(d.shape, jnp.int32)
    for k in range(1, QW_BUCKETS):
        b = b + (d >= (1 << k)).astype(jnp.int32)
    return b


def profile_lanes(program: Program, opts: RuntimeOptions, st: RtState,
                  tail0, res, drain_facts, muted2):
    """The per-behaviour profiler lanes (≙ the fork's per-actor
    --ponyanalysis records, analysis.h:16-31, re-based on the cohort —
    the TPU unit of attribution). ONLY traced when opts.analysis >= 1:
    the caller gates the call itself, so at level 0 none of this exists
    in the jaxpr (the zero-cost test traps this function to prove it).

    All facts are recomputed from the ring head/tail advances rather
    than threaded out of the dispatch kernels, so ONE implementation
    covers both dispatch formulations (the XLA scan and the fused
    Pallas kernel) and their semantics cannot drift:

      - beh_runs[g]       += messages of behaviour g dispatched this
                             tick (ring slots [head0, head1) — the
                             drained prefix, yield-shortened included);
      - qwait_hist[c*QW+k] += dispatched messages of device cohort c
                             whose delivery→dispatch wait fell in
                             bucket k (deltas against the qwait_enq
                             stamps written at delivery);
      - coh_mute_ticks[c] += actors of device cohort c muted at end of
                             tick (actor-ticks: the integral of
                             muted_now);
      - beh_delivered[g]  += messages of behaviour g accepted into
                             mailboxes this tick (tail advance over the
                             post-delivery tables; host cohorts count —
                             the host drains those rows);
      - beh_rejected[g]   += this tick's capacity rejections by target
                             behaviour (the compacted spill's gid
                             words — per-tick semantics match
                             n_rejected: a parked message re-rejected
                             next tick counts again);
      - qwait_enq[type]    = enqueue-step stamps for freshly delivered
                             ring slots (read back by the next ticks'
                             deltas above).

    `drain_facts` = [(cohort, head_before, head_after)] in
    device-cohort order. Returns the six updated state fields."""
    s_now = st.step_no[0]
    beh_runs = st.beh_runs
    beh_del = st.beh_delivered
    beh_rej = st.beh_rejected
    coh_mt = st.coh_mute_ticks
    qw_hist = st.qwait_hist
    qw_enq = dict(st.qwait_enq)
    def _planes(ch):        # ring-slot planes of a cohort, and its depth
        cap = ch.mailbox_cap
        return jnp.arange(cap, dtype=jnp.int32)[:, None], cap

    def _count(mask):
        return jnp.sum(mask.astype(jnp.int32))

    # --- dispatch side: runs per behaviour + queue-wait histogram.
    for di, (ch, head0, head1) in enumerate(drain_facts):
        cname = ch.atype.__name__
        ci, cap = _planes(ch)
        n_con = head1 - head0
        # Ring slot ci held a message drained this tick iff its
        # monotonic count fell in [head0, head0 + n_con).
        drained = ((ci - head0[None, :]) % cap) < n_con[None, :]
        gid = st.buf[cname][:, 0, :]                 # [cap, rows]
        for b in ch.behaviours:
            beh_runs = beh_runs.at[b.global_id].add(
                _count(drained & (gid == b.global_id)))
        bidx = _qwait_bucket(s_now - qw_enq[cname])
        for k in range(QW_BUCKETS):
            qw_hist = qw_hist.at[di * QW_BUCKETS + k].add(
                _count(drained & (bidx == k)))
        coh_mt = coh_mt.at[di].add(
            _count(muted2[ch.local_start:ch.local_stop]))

    # --- delivery side: acceptances per behaviour + enqueue stamps.
    for ch in program.cohorts:
        cname = ch.atype.__name__
        s0, s1 = ch.local_start, ch.local_stop
        ci, cap = _planes(ch)
        n_new = res.tail[s0:s1] - tail0[s0:s1]
        fresh = ((ci - tail0[None, s0:s1]) % cap) < n_new[None, :]
        gid = res.buf[cname][:, 0, :]
        for b in ch.behaviours:
            beh_del = beh_del.at[b.global_id].add(
                _count(fresh & (gid == b.global_id)))
        if cname in qw_enq:                          # device cohorts
            qw_enq[cname] = jnp.where(fresh, s_now, qw_enq[cname])

    # --- rejects by target behaviour (the compacted spill is exactly
    # this tick's rejections, re-rejections of parked entries included).
    sp_gid = res.spill.words[0]
    sp_ok = res.spill.tgt >= 0
    for g in range(len(program.behaviour_table)):
        beh_rej = beh_rej.at[g].add(_count(sp_ok & (sp_gid == g)))

    return beh_runs, beh_del, beh_rej, coh_mt, qw_hist, qw_enq


def phase_cost_lanes(st: RtState, listed_tgt, drain_facts, nproc_total,
                     n_spawned, n_destroyed, rebuild_slots):
    """Per-phase window telemetry (ISSUE 19): accumulate one
    deterministic work-unit tally per scheduler-tick
    phase into st.phase_cost (state.PHASE_NAMES order). ONLY traced when
    opts.analysis >= 1 — the caller gates the call itself, so at level 0
    none of this exists in the jaxpr (the zero-cost test traps this
    function exactly like profile_lanes).

    The tallies are recomputed from facts every dispatch formulation
    already produces (the profile_lanes recomputation trick), so the
    lanes are bit-identical whichever formulation ran:

      - delivery += valid delivery-list entries gathered this tick
                    (spill retries + host injections + routed sends):
                    `listed_tgt`, the list's targets, >= 0 where valid
                    (route.Routed.listed_tgt);
      - drain    += mailbox ring slots consumed (head advances, the
                    yield-shortened prefix included — >= dispatch:
                    drained-but-dropped badmsg rows count here only);
      - dispatch += behaviours actually run (the n_processed increment);
      - gc_mark  += spawn/destroy bookkeeping rows touched (claimed
                    spawns + completed destroys — the slot-lifecycle
                    work the GC pass marks from);
      - rebuild  += indices the delivery rebuild's gathers read: over
                    the cohorts and the rank blocks each ran (as deep as
                    its own fullest mailbox of the tick), the ranks
                    that mailbox holds of the block (8, or what is left
                    of its depth: 1 for a cohort of self-senders) x the
                    cohort's rows a full-width block; 8 ranks x M a
                    compacted one, M = ceil(rows / 8), which a block is
                    from the first whose rows with a message in it fit
                    in M (delivery.rebuild_tables).

    Work units, not wall time: wall/bytes attribution is the measured
    layer's job (costs.py)."""
    pc = st.phase_cost
    delivery = jnp.sum((listed_tgt >= 0).astype(jnp.int32))
    drained = jnp.int32(0)
    for _ch, head0, head1 in drain_facts:
        drained = drained + jnp.sum(head1 - head0)
    pc = pc.at[PHASE_NAMES.index("delivery")].add(delivery)
    pc = pc.at[PHASE_NAMES.index("drain")].add(drained)
    pc = pc.at[PHASE_NAMES.index("dispatch")].add(nproc_total)
    pc = pc.at[PHASE_NAMES.index("gc_mark")].add(n_spawned + n_destroyed)
    pc = pc.at[PHASE_NAMES.index("rebuild")].add(rebuild_slots)
    return pc


def trace_span_lanes(program: Program, opts: RuntimeOptions, st: RtState,
                     drain_facts, base, shard):
    """Causal-tracing lanes (PROFILE.md §10; ≙ the fork's per-event
    analysis rows following one message send→dispatch,
    analysis.c:587-692 — per MESSAGE here, where profile_lanes is per
    aggregate). ONLY traced when opts.tracing: the caller gates the
    call itself, so with tracing off none of this exists in the jaxpr
    (tests/test_tracing.py traps this function to prove it).

    Works entirely from the ring-advance facts (profile_lanes'
    recomputation trick), so ONE implementation covers both dispatch
    formulations (the XLA scan and the fused Pallas kernel) and both
    delivery formulations (plan and cosort):

      - every drained ring slot whose trace_id side lane is >= 0
        becomes a SPAN: a fresh even span id from the per-shard
        monotonic counter (host spans are odd — tracing.py owns the
        scheme), recorded in the bounded span ring as (trace_id,
        span_id, parent_span, behaviour_gid, actor_gid, enqueue_tick
        [the qwait_enq delivery stamp], dispatch_tick, retire_tick);
        overflow between two host drains drops and counts;
      - outbox PROPAGATION rows: entry (b, m, r) of the cohort's
        outbox inherits (trace_id, span_id) of the message batch slot
        b dispatched on lane r — sends AND spawns (constructor
        messages ride the same outbox) continue the causal chain; the
        rows-minor [batch, ms, rows] flatten matches both the scan's
        stack and the fused kernel's layout, so neither dispatch path
        needs to know tracing exists.

    `drain_facts` = [(cohort, head_before, head_after)] in
    device-cohort order. Returns (span_data, span_count, span_dropped,
    span_next, [per-cohort [2, e_c] propagation rows])."""
    p = program.shards
    ts_cap = opts.trace_slots
    s_now = st.step_no[0]
    span_data = st.span_data
    span_count = st.span_count[0]
    span_dropped = st.span_dropped[0]
    span_next = st.span_next[0]
    tr_out = []
    for (ch, head0, head1) in drain_facts:
        cname = ch.atype.__name__
        cap = ch.mailbox_cap
        ci = jnp.arange(cap, dtype=jnp.int32)[:, None]
        rows = ch.local_capacity
        batch, ms = ch.batch, ch.max_sends
        n_con = head1 - head0
        drained = ((ci - head0[None, :]) % cap) < n_con[None, :]
        tid = st.trace_buf[cname][:, 0, :]            # [cap, rows]
        tparent = st.trace_buf[cname][:, 1, :]
        traced = drained & (tid >= 0)
        e = rows * batch * ms

        def busy(_):
            """Span allocation + ring write + propagation — runs under
            a cond so ticks where this COHORT dispatched no traced
            message skip the compaction sort and scatters entirely
            (the ev-ring discipline, §5b: the structural cost of
            tracing scales with traced traffic, not with enabling the
            knob)."""
            sd = span_data
            flat = traced.reshape(-1)                 # cap-major order
            rank = jnp.cumsum(flat.astype(jnp.int32)) - 1
            total = jnp.sum(flat.astype(jnp.int32))
            sid_flat = jnp.where(
                flat, ((span_next + rank) * p + shard) * 2 + 2,
                jnp.int32(0))
            k_sp = min(ts_cap, cap * rows)
            perm, valid2, _tot = compact_mask(flat, k_sp)
            pos = span_count + jnp.arange(k_sp, dtype=jnp.int32)
            ok = valid2 & (pos < ts_cap)
            posc = jnp.where(ok, pos, ts_cap)
            actor = jnp.broadcast_to(
                (base + ch.local_start
                 + jnp.arange(rows, dtype=jnp.int32))[None, :],
                (cap, rows)).reshape(-1)
            vals = (tid.reshape(-1), sid_flat, tparent.reshape(-1),
                    st.buf[cname][:, 0, :].reshape(-1), actor,
                    st.qwait_enq[cname].reshape(-1),
                    jnp.broadcast_to(s_now, (cap * rows,)),
                    jnp.broadcast_to(s_now + 1, (cap * rows,)))
            for ri, v in enumerate(vals):
                sd = sd.at[ri, posc].set(
                    jnp.where(ok, v[perm], 0), mode="drop")
            # --- propagation rows for this cohort's outbox.
            sid = sid_flat.reshape(cap, rows)
            tid_b, sid_b = [], []
            for b in range(batch):
                slot = (head0 + b) % cap
                tb, sb = tid[0], sid[0]
                for cslot in range(1, cap):   # static select chain,
                    sel = slot == cslot       # like state.ring_take
                    tb = jnp.where(sel, tid[cslot], tb)
                    sb = jnp.where(sel, sid[cslot], sb)
                okb = (b < n_con) & (tb >= 0)
                tid_b.append(jnp.where(okb, tb, jnp.int32(-1)))
                sid_b.append(jnp.where(okb, sb, jnp.int32(0)))
            if ms:
                tid_e = jnp.broadcast_to(
                    jnp.stack(tid_b)[:, None, :],
                    (batch, ms, rows)).reshape(e)
                sid_e = jnp.broadcast_to(
                    jnp.stack(sid_b)[:, None, :],
                    (batch, ms, rows)).reshape(e)
            else:
                tid_e = jnp.full((0,), -1, jnp.int32)
                sid_e = jnp.zeros((0,), jnp.int32)
            return (sd,
                    jnp.minimum(span_count + total, ts_cap),
                    span_dropped + jnp.maximum(
                        0, span_count + total - ts_cap),
                    span_next + total,
                    jnp.stack([tid_e, sid_e]))

        def quiet(_):
            return (span_data, span_count, span_dropped, span_next,
                    jnp.stack([jnp.full((e,), -1, jnp.int32),
                               jnp.zeros((e,), jnp.int32)]))

        (span_data, span_count, span_dropped, span_next,
         tr_pair) = lax.cond(jnp.any(traced), busy, quiet, operand=None)
        tr_out.append(tr_pair)
    return span_data, span_count, span_dropped, span_next, tr_out


# RtState's profiler fields, in the order `profile_lanes` returns them.
PROFILE_FIELDS = ("beh_runs", "beh_delivered", "beh_rejected",
                  "coh_mute_ticks", "qwait_hist", "qwait_enq", "phase_cost")


def event_ring(k: TickStatic, st: RtState, w, ring, error_rows, life,
               became_muted, occ_after):
    """--- 5b. per-event trace ring (analysis level 3 only; ≙ the
    fork's per-event analysis rows, analysis.c:587-692): record the
    tick's TRANSITIONS (mute, unmute, overload-on, spawn, destroy,
    error) as (event, actor, step) triples compacted into a bounded
    ring the host drains at window boundaries. Traced only when
    enabled; and under a cond so event-free ticks skip the
    compaction sort. `ring` = (ev_data, ev_count, ev_dropped) as the
    tick found them; `life`: the rows after the destroys."""
    opts, nl, alive, muted = k.opts, k.nl, life.alive, life.muted
    ev_data, ev_count, ev_dropped = ring
    released_ev = st.muted & ~muted & alive
    hot = rows_of(k.program, "overload_occ")
    over_ev = (occ_after > hot) & ~(w.occ0 > hot)
    spawn_ev = alive & ~st.alive
    destroy_ev = st.alive & ~alive
    err_ev = jnp.zeros((nl,), jnp.bool_)
    for s0, errs in error_rows:
        if errs is None:
            continue
        errf = errs[0]
        rows_ = s0 + jnp.arange(errf.shape[0], dtype=jnp.int32)
        err_ev = err_ev.at[rows_].max(errf)
    classes = [(1, became_muted), (2, released_ev), (3, over_ev),
               (4, spawn_ev), (5, destroy_ev), (6, err_ev)]
    masks = jnp.concatenate([m for _, m in classes])
    ev_cap = opts.analysis_events

    # A tick can produce at most len(classes)*nl events.
    k_ev = min(ev_cap, masks.shape[0])

    def record(_):
        codes = jnp.concatenate(
            [jnp.full((nl,), cde, jnp.int32) for cde, _ in classes])
        actors = w.base + jnp.tile(
            jnp.arange(nl, dtype=jnp.int32), len(classes))
        perm2, valid2, total2 = compact_mask(masks, k_ev)
        pos = ev_count + jnp.arange(k_ev, dtype=jnp.int32)
        ok = valid2 & (pos < ev_cap)
        posc = jnp.where(ok, pos, ev_cap)
        ev = ev_data
        ev = ev.at[0, posc].set(
            jnp.where(ok, codes[perm2], 0), mode="drop")
        ev = ev.at[1, posc].set(
            jnp.where(ok, actors[perm2], 0), mode="drop")
        ev = ev.at[2, posc].set(
            jnp.full((k_ev,), st.step_no[0] + 1), mode="drop")
        return (ev, jnp.minimum(ev_count + total2, ev_cap),
                ev_dropped + jnp.maximum(
                    0, ev_count + total2 - ev_cap))

    return lax.cond(
        jnp.any(masks), record,
        lambda _: (ev_data, ev_count, ev_dropped), operand=None)


def vote_lanes(k: TickStatic, occ_after, muted2, counts, qw_hist2):
    """The vote's telemetry aggregates over this shard: (occ_sum,
    occ_max, n_muted_now, n_over_now, *counts, qw_p99), `counts` the
    cumulative (rejected, badmsg, deadletter, mutes). Real reductions
    at analysis >= 1, else constant zeros that XLA folds away."""
    if k.opts.analysis < 1:
        return (jnp.int32(0),) * 9
    occ_sum = jnp.sum(occ_after)
    occ_max = jnp.max(occ_after)
    n_muted_now = jnp.sum(muted2.astype(jnp.int32))
    n_over_now = jnp.sum(
        (occ_after > rows_of(k.program, "overload_occ")).astype(jnp.int32))
    # Worst-cohort queue-wait p99 of the cumulative histograms —
    # in-trace twin of analysis.hist_percentile (bucket k holds
    # waits in [2^k, 2^(k+1)); the reported value is the lower
    # bound of the first bucket whose cumulative count reaches
    # ceil(0.99 * total)). Rides the aux so the host's window
    # controller sees queue-wait pressure with no extra fetch.
    nd_prof = qw_hist2.shape[0] // QW_BUCKETS
    if nd_prof > 0:
        h2 = qw_hist2.reshape(nd_prof, QW_BUCKETS)
        tot = jnp.sum(h2, axis=1)
        need = jnp.maximum(1, (tot * 99 + 99) // 100)
        first = jnp.argmax(
            jnp.cumsum(h2, axis=1) >= need[:, None],
            axis=1).astype(jnp.int32)
        qw_p99 = jnp.max(jnp.where(
            tot > 0, jnp.left_shift(jnp.int32(1), first),
            jnp.int32(0)))
    else:
        qw_p99 = jnp.int32(0)
    return (occ_sum, occ_max, n_muted_now, n_over_now, *counts, qw_p99)
