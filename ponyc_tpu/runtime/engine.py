"""The dispatch step: one scheduler tick over the whole actor world, jitted.

≙ the reference's hot loop (SURVEY.md §3.3): scheduler `run`
(src/libponyrt/sched/scheduler.c:953-1090) popping actors and
`ponyint_actor_run` (src/libponyrt/actor/actor.c:383-549) draining up to
`batch` messages per actor through `type->dispatch`. On TPU there is no
work-stealing — the entire world advances in lockstep:

  per device cohort (actors of one type, contiguous per-shard rows):
      gather  ≤batch messages per actor from the mailbox table
      scan    over batch slots; per slot a `lax.switch` over the type's
              behaviours (≙ the generated dispatch switch, genfun.c),
              vmapped over the cohort's actors
      collect sends / exit / yield effects functionally
  route   (mesh only) bucket every produced message by target shard and
          exchange with one `lax.all_to_all` over the ICI — the
          communication backend the single-process reference never needed
          (SURVEY.md §2.4); bucket overflow parks messages in the sender
          shard's route-spill, muting the sender
  deliver one stable sort + scatter per shard writes every message whose
          target lives here (see delivery.py), mute/unmute updates
  vote    quiescence = psum over shards of pending-work bits — the
          collective analog of the CNF/ACK token protocol
          (scheduler.c:303-480)

Work-stealing, victim selection and scaling-sleep (scheduler.c:485-935)
have no TPU analog — idle actors cost one masked lane, not a core.

The same traced function serves single-chip (P=1: no collectives, plain
jit) and meshed execution (shard_map over an 'actors' axis); per-shard
"scalars" are [1]-shaped so local and global layouts coincide.
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..api import Context
from ..config import RuntimeOptions
from ..ops import pack
from ..ops.segment import (compact_mask, counts_by_key, marks_of,
                           stable_sort_carrying)
from ..program import Cohort, Program
from .delivery import Entries, deliver, empty_mute_slots, mute_ref_slots
from .state import (PHASE_NAMES, QW_BUCKETS, ROUTE_COUNTERS, PhaseCursor,
                    RtState, layout_sizes, phase_scope, pool_index)


class StepAux(NamedTuple):
    """Small per-step scalars fetched by the host driver (≙ the scheduler's
    control-message reads + quiescence vote, scheduler.c:303-480). All
    entries are mesh-wide aggregates (replicated when sharded)."""
    device_pending: jnp.ndarray  # bool — any device mailbox/spill work left
    host_pending: jnp.ndarray    # bool — host-cohort mailboxes non-empty
    exit_flag: jnp.ndarray       # bool — some behaviour called ctx.exit
    exit_code: jnp.ndarray       # int32
    spill_overflow: jnp.ndarray  # bool — fatal: a spill buffer exceeded
    spawn_fail: jnp.ndarray      # bool — fatal: ctx.spawn found no slot
    blob_fail: jnp.ndarray       # bool — fatal: ctx.blob_alloc found the
    #   POOL exhausted (≙ pony_alloc exhausting the heap; raise
    #   RuntimeOptions.blob_slots)
    blob_budget_fail: jnp.ndarray  # bool — fatal: ctx.blob_alloc ran
    #   past the actor's per-tick BLOB_DISPATCHES reservation budget
    #   (free slots may remain; raise the class's BLOB_DISPATCHES)
    any_muted: jnp.ndarray       # bool — some actor still carries a mute
    #   flag; run() uses it for bounded CLEANUP ticks at quiescence so a
    #   terminated world ends unmuted (the unmute pass lags the drain
    #   that satisfies it by one tick)
    n_processed: jnp.ndarray     # int32 — *cumulative* behaviours run
    n_delivered: jnp.ndarray     # int32 — *cumulative* deliveries
    # (cumulative = state counters; the host accumulates mod-2^32 deltas,
    # so fetches may be arbitrarily far apart as long as fewer than 2^31
    # events occur between two fetches.)
    # Telemetry aggregates (≙ --ponyanalysis, analysis.c): traced as real
    # reductions only when opts.analysis >= 1, else constant zeros that
    # XLA folds away — opt-in observability at zero steady-state cost.
    occ_sum: jnp.ndarray         # int32 — total queued messages
    occ_max: jnp.ndarray         # int32 — deepest mailbox
    n_muted_now: jnp.ndarray     # int32 — actors currently muted
    n_overloaded_now: jnp.ndarray  # int32 — occupancy > overload threshold
    # Cumulative mesh-wide counters (zeros unless analysis >= 1) so the
    # CSV window writer needs no extra device fetches.
    n_rejected: jnp.ndarray      # int32
    n_badmsg: jnp.ndarray        # int32
    n_deadletter: jnp.ndarray    # int32
    n_mutes: jnp.ndarray         # int32
    qw_p99: jnp.ndarray          # int32 — worst per-cohort queue-wait
    #   p99 (ticks, 2^k bucket lower bound) of the CUMULATIVE on-device
    #   histograms (profile_lanes), mesh max. Zero unless analysis >= 1.
    #   The adaptive window controller (runtime/controller.py) shrinks
    #   the quiesce window when this climbs past the window length —
    #   long windows trade host-event latency for dispatch amortisation,
    #   and this lane is the device's vote that the trade went bad.
    spawn: dict = {}             # row pressure, a program with device
    #   spawns only ({} elsewhere: no leaf, the window's HLO unchanged).
    #   "room" int32 — over the spawn targets, the least (free rows -
    #   rows the NEXT tick's reservations will ask for), read off this
    #   tick's final state: below 0 the window ends (aux_go) and the
    #   host collects before the next tick (runtime.py run()); "low"
    #   int32 — the least room >= 0 any tick of the run has left: how
    #   near the world came to a refused spawn; "spawned" int32 —
    #   *cumulative* device spawns (the state's n_spawned, mesh-wide).


def _ring_take(buf_rows, slot):
    """Pull ring-slot `slot[r]` of every actor r: [cap, w1, R] × [R] →
    [w1, R]. The per-lane index varies only over the small static `cap`
    axis, so a static select chain keeps every op a full-width vector op
    (a gather along a tiny major axis would defeat the lane layout —
    see state.py's layout note)."""
    cap = buf_rows.shape[0]
    out = buf_rows[0]
    for c in range(1, cap):
        out = jnp.where((slot == c)[None, :], buf_rows[c], out)
    return out


def _bcast_lanes(v, dtype, lanes: int):
    """Canonicalise a behaviour output to a [lanes] vector (user code may
    return trace-time constants — Python scalars — for some lanes-wide
    quantities)."""
    return jnp.broadcast_to(jnp.asarray(v, dtype), (lanes,))



def eval_behaviour(bdef, st, payload, ids_vec, *, msg_words: int,
                   field_specs, field_dtypes, lanes: int, max_sends: int,
                   spawn_resv=None, spawn_meta=None, blob=None):
    """Shared behaviour-evaluation core: build the Context, tag typed
    refs, run the traced body, validate + broadcast the state update,
    and collect when-masked send planes padded to the send budget.
    Used by BOTH dispatch formulations (the planar XLA branch below and
    ops/fused_dispatch's kernel) so their semantics cannot drift.
    `blob` (device pool enabled only): an api.BlobPoolView the blob ops
    mutate eagerly — see its docstring for why sequential application
    is exact. Returns (ctx, st2, tgts, words)."""
    w1 = 1 + msg_words
    ctx = Context(ids_vec, msg_words, spawn_resv=spawn_resv,
                  spawn_meta=spawn_meta, blob=blob)
    args = pack.unpack_args(bdef.arg_specs, payload)
    if blob is not None:
        # Blob handles are dereferenceable only on their pool's shard;
        # migration (engine._route) re-homes payloads with their routed
        # messages, so mailbox handles are local by the time they
        # dispatch. The residue — host injections without near=, or
        # migration drops — reads as null (-1) and counts: defined,
        # loud, never a wrong read. ≙ nothing in the reference (it is
        # single-node; there is no remote heap to dereference).
        nulled = []
        for spec, a in zip(bdef.arg_specs, args):
            if pack.is_blob(spec):
                a = jnp.asarray(a, jnp.int32)
                slot = pack.blob_slot(a)
                local_ok = ((a >= 0) & (slot >= blob.base)
                            & (slot < blob.base + blob.nslots))
                remote = (a >= 0) & ~local_ok
                blob.n_remote = blob.n_remote + jnp.sum(
                    (remote & blob.take).astype(jnp.int32))
                a = jnp.where(local_ok, a, jnp.int32(-1))
            nulled.append(a)
        args = nulled
    # Typed Ref[T] state fields and args enter the behaviour as PLAIN
    # arrays whose trace-time identity is tagged with the declared
    # type (pack.RefTypes), so Context.send verifies wiring at trace
    # time (≙ type/safeto.c sendability; the verify pass of the
    # build) without touching how refs behave under jnp ops.
    for k, v in st.items():
        ctx.ref_types.tag(v, pack.ref_target(field_specs[k]))
        ctx.cap_types.tag(v, pack.cap_mode(field_specs[k]))
    for spec, a in zip(bdef.arg_specs, args):
        ctx.ref_types.tag(a, pack.ref_target(spec))
        ctx.cap_types.tag(a, pack.cap_mode(spec))
    st2 = bdef.fn(ctx, dict(st), *args)
    if st2 is None:
        raise TypeError(
            f"behaviour {bdef} must return the (possibly updated) state "
            "dict")
    if set(st2.keys()) != set(st.keys()):
        raise TypeError(
            f"behaviour {bdef} changed the state fields: "
            f"{sorted(st2)} vs {sorted(st)}")
    for k, v in st2.items():
        want = pack.ref_target(field_specs[k])
        got = ctx.ref_types.lookup(v)
        if want is not None and got is not None and got != want:
            raise TypeError(
                f"sendability: behaviour {bdef} stores a Ref[{got}] "
                f"into field {k!r} declared Ref[{want}]")
        # Iso payloads are moved-unique (≙ cap.c/safeto.c): a handle the
        # behaviour just moved (sent as an Iso parameter) may not ALSO
        # be retained in state — including leaving an Iso field
        # untouched after moving it (overwrite with -1 to consume).
        moved = (None if pack.concrete_null_handle(v)
                 else ctx.cap_moves.was_moved(v))
        if moved is not None:
            raise TypeError(
                f"capability: behaviour {bdef} retains a moved iso "
                f"payload in field {k!r} (moved by {moved}); an iso is "
                "moved-unique — clear the field (e.g. -1) or use Val "
                "for shared-immutable payloads")
        # Store lattice (≙ is_cap_sub_cap): the stored value's
        # capability provenance must cover the field's declared mode
        # (a shared val cannot become a unique iso; a tag cannot
        # become readable).
        src = (None if pack.concrete_null_handle(v)
               else ctx.cap_types.lookup(v))
        dst = pack.cap_mode(field_specs[k])
        if not pack.cap_store_ok(src, dst):
            raise TypeError(
                f"capability: behaviour {bdef} stores a {src} payload "
                f"into field {k!r} declared {dst.capitalize()} — a "
                f"{src} value cannot grant the rights {dst} requires "
                "(is_cap_sub_cap, type/cap.c)")
    # An iso-provenance value stored into MORE THAN ONE field aliases a
    # unique (≙ alias.c): every field keeping it is a distinct owner.
    # A trn is WRITE-unique (cap.c): keeping it in the field it came
    # from is free, and Box/Tag stores alias it (read views — Pony's
    # trn+box sharing); but a CONSUMING store into a *different*
    # Trn/Mut/Val field (ownership/freeze, ≙ consume) must be the
    # value's only remaining appearance.
    origin_field = {}
    for k, v in st.items():
        origin_field.setdefault(id(v), k)
    iso_seen = {}
    trn_consumed = {}
    trn_retained = {}      # keeps + aliases (anything but the consume)
    for k, v in st2.items():
        if pack.concrete_null_handle(v):
            continue
        src = ctx.cap_types.lookup(v)
        if src == "iso":
            first = iso_seen.get(id(v))
            if first is not None:
                raise TypeError(
                    f"capability: behaviour {bdef} stores one iso "
                    f"payload into BOTH fields {first!r} and {k!r} — "
                    "an iso has exactly one owner (alias.c)")
            iso_seen[id(v)] = k
        elif src == "trn":
            dst = pack.cap_mode(field_specs[k])
            consuming = (dst in pack.CONSUMING_DSTS
                         and origin_field.get(id(v)) != k)
            if consuming:
                first = trn_consumed.get(id(v))
                if first is not None:
                    raise TypeError(
                        f"capability: behaviour {bdef} consumes one trn "
                        f"payload into BOTH fields {first!r} and {k!r} "
                        "— a trn is write-unique (cap.c); alias it Box "
                        "for read sharing")
                trn_consumed[id(v)] = k
            else:
                trn_retained.setdefault(id(v), k)
    for idv, kc in trn_consumed.items():
        ka = trn_retained.get(idv)
        if ka is not None:
            raise TypeError(
                f"capability: behaviour {bdef} consumes a trn payload "
                f"into field {kc!r} and ALSO retains it in {ka!r} — "
                "use-after-consume (alias.c)")
    st2 = {k: _bcast_lanes(v, field_dtypes[k], lanes)
           for k, v in st2.items()}
    if len(ctx.sends) > max_sends:
        raise RuntimeError(
            f"behaviour {bdef} performs {len(ctx.sends)} sends but the "
            f"type's send budget is {max_sends}; set MAX_SENDS = "
            f"{len(ctx.sends)} on the actor class")
    tgts, words = [], []
    for (t, w, when) in ctx.sends:
        t = _bcast_lanes(t, jnp.int32, lanes)
        when = _bcast_lanes(when, jnp.bool_, lanes)
        w = jnp.broadcast_to(w.reshape(w1, -1), (w1, lanes))
        tgts.append(jnp.where(when, t, jnp.int32(-1)))
        words.append(w)
    for _ in range(max_sends - len(ctx.sends)):
        tgts.append(jnp.full((lanes,), -1, jnp.int32))
        words.append(jnp.zeros((w1, lanes), jnp.int32))
    return ctx, st2, tgts, words


def _make_branch(bdef, msg_words: int, max_sends: int, field_dtypes,
                 field_specs, spawn_sites, spawn_meta, effects,
                 lanes: int):
    """Wrap one behaviour as a *planar* evaluator: it runs on ALL `lanes`
    actors of the cohort at once (state fields, args, and effect masks
    are [lanes] vectors) and the dispatcher selects its outputs where the
    message's behaviour id matches. This is exactly what `vmap` over
    `lax.switch` executes (batched switch runs every branch and selects),
    but written planar so no actor-major [lanes, small] intermediate is
    ever materialised (see state.py's layout note).

    spawn_sites: ordered (target_name, n_sites) static budget — every
    branch emits claims in this exact layout. effects: trace-time mutable
    record of which effects any behaviour of the cohort used (lets the
    engine skip dead scatters)."""
    w1 = 1 + msg_words

    def branch(st, payload, ids_vec, resv_k, blob_in=None, take=None):
        bv = None
        if blob_in is not None:
            # (pool arrays threaded sequentially through the branches —
            # see api.BlobPoolView for why no cross-branch select is
            # needed; resv row may be zero-sites for receive-only types.)
            from ..api import BlobPoolView
            bdata, bused, blen, bgen, bbase, bresv, bover = blob_in
            bv = BlobPoolView(bdata, bused, blen, bgen, bbase,
                              (take if take is not None
                               else jnp.ones((lanes,), jnp.bool_)),
                              bresv if (bresv is not None
                                        and bresv.shape[0]) else None,
                              budget_over=bover)
        ctx, st2, tgts, words = eval_behaviour(
            bdef, st, payload, ids_vec, msg_words=msg_words,
            field_specs=field_specs, field_dtypes=field_dtypes,
            lanes=lanes, max_sends=max_sends, spawn_resv=resv_k,
            spawn_meta=spawn_meta, blob=bv)
        effects["destroy"] = effects["destroy"] or ctx.destroy_called
        effects["error"] = effects["error"] or ctx.error_called
        effects["sync_init"] = (effects["sync_init"]
                                or bool(ctx.sync_inits))
        claims = []
        inits = []
        for tname, n in spawn_sites:
            got = [_bcast_lanes(g, jnp.int32, lanes)
                   for g in ctx.spawn_claims.get(tname, [])]
            got += [jnp.full((lanes,), -1, jnp.int32)] * (n - len(got))
            claims.append(got)
            # Sync-constructor field values per site (spawn_sync): the
            # `has` mask selects them over zero-defaults at claim time.
            t_specs = spawn_meta[tname]
            t_dt = {f: (jnp.float32 if s is pack.F32 else jnp.int32)
                    for f, s in t_specs.items()}
            site_map = ctx.sync_inits.get(tname, {})
            has_l, vals_l = [], {f: [] for f in t_specs}
            for s_i in range(n):
                ent = site_map.get(s_i)
                if ent is None:
                    has_l.append(jnp.zeros((lanes,), jnp.bool_))
                    for f, sp in t_specs.items():
                        d = pack.null_word(sp)
                        vals_l[f].append(jnp.full((lanes,), d, t_dt[f]))
                else:
                    ist, ok = ent
                    has_l.append(_bcast_lanes(ok, jnp.bool_, lanes))
                    for f in t_specs:
                        vals_l[f].append(
                            _bcast_lanes(ist[f], t_dt[f], lanes))
            inits.append((has_l, vals_l))
        b = jnp.bool_
        blob_out = None
        if bv is not None:
            blob_out = (bv.data, bv.used, bv.len_, bv.gen, bv.fail,
                        bv.budget_fail, bv.n_alloc, bv.n_free,
                        bv.n_remote,
                        _bcast_lanes(bv.alloced, jnp.bool_, lanes))
        return (st2, (tgts, words),
                (_bcast_lanes(ctx.exit_flag, b, lanes),
                 _bcast_lanes(ctx.exit_code, jnp.int32, lanes)),
                _bcast_lanes(ctx.yield_flag, b, lanes),
                claims, inits,
                _bcast_lanes(ctx.spawn_fail, b, lanes),
                _bcast_lanes(ctx.destroy_flag, b, lanes),
                (_bcast_lanes(ctx.error_flag, b, lanes),
                 _bcast_lanes(ctx.error_code, jnp.int32, lanes),
                 _bcast_lanes(ctx.error_loc, jnp.int32, lanes)),
                blob_out)

    return branch


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
    cap = opts.mailbox_cap
    s_now = st.step_no[0]
    beh_runs = st.beh_runs
    beh_del = st.beh_delivered
    beh_rej = st.beh_rejected
    coh_mt = st.coh_mute_ticks
    qw_hist = st.qwait_hist
    qw_enq = dict(st.qwait_enq)
    ci = jnp.arange(cap, dtype=jnp.int32)[:, None]   # ring-slot planes

    def _count(mask):
        return jnp.sum(mask.astype(jnp.int32))

    # --- dispatch side: runs per behaviour + queue-wait histogram.
    for di, (ch, head0, head1) in enumerate(drain_facts):
        cname = ch.atype.__name__
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


def phase_cost_lanes(st: RtState, all_e, drain_facts, nproc_total,
                     n_spawned, n_destroyed, rebuild_slots):
    """Per-phase window telemetry (the device-cost observatory, ISSUE
    19): accumulate one deterministic work-unit tally per scheduler-tick
    phase into st.phase_cost (state.PHASE_NAMES order). ONLY traced when
    opts.analysis >= 1 — the caller gates the call itself, so at level 0
    none of this exists in the jaxpr (the zero-cost test traps this
    function exactly like profile_lanes).

    The tallies are recomputed from facts every dispatch formulation
    already produces (the profile_lanes recomputation trick), so the
    lanes are bit-identical whichever formulation ran:

      - delivery += valid delivery-list entries gathered this tick
                    (spill retries + host injections + routed sends);
      - drain    += mailbox ring slots consumed (head advances, the
                    yield-shortened prefix included — >= dispatch:
                    drained-but-dropped badmsg rows count here only);
      - dispatch += behaviours actually run (the n_processed increment);
      - gc_mark  += spawn/destroy bookkeeping rows touched (claimed
                    spawns + completed destroys — the slot-lifecycle
                    work the GC pass marks from);
      - rebuild  += indices the delivery rebuild's gathers read: over
                    the cohorts and the rank blocks each ran (as deep as
                    its own fullest mailbox of the tick), 8 ranks x the
                    cohort's rows a full-width block; 8 ranks x M a
                    compacted one, M = ceil(rows / 8), which a block is
                    from the first whose rows with a message in it fit
                    in M (delivery.rebuild_tables).

    Work units, not wall time: wall/bytes attribution is the measured
    layer's job (costs.py)."""
    pc = st.phase_cost
    delivery = jnp.sum((all_e.tgt >= 0).astype(jnp.int32))
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
    cap = opts.mailbox_cap
    p = program.shards
    ts_cap = opts.trace_slots
    s_now = st.step_no[0]
    span_data = st.span_data
    span_count = st.span_count[0]
    span_dropped = st.span_dropped[0]
    span_next = st.span_next[0]
    ci = jnp.arange(cap, dtype=jnp.int32)[:, None]
    tr_out = []
    for (ch, head0, head1) in drain_facts:
        cname = ch.atype.__name__
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
                    sel = slot == cslot       # like _ring_take
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


def _cohort_dispatch(cohort: Cohort, opts: RuntimeOptions, noyield: bool,
                     program: Program):
    """Build the planar per-cohort drain loop.

    ≙ ponyint_actor_run (actor.c:383-549): pop ≤batch app messages,
    dispatch each, honour yield (fork: actor.c:675-679), count
    consumption — for every actor of the cohort at once, as [rows]-wide
    vector ops (actors on the 128 TPU lanes, batch slots iterated by a
    lax.scan whose carries are all lane-shaped).
    """
    msg_words = opts.msg_words          # OUTBOX width (program-wide max)
    ms = cohort.max_sends
    batch = cohort.batch
    cap = opts.mailbox_cap
    rows = cohort.local_capacity
    w1 = 1 + msg_words
    # This cohort's own mailbox width (≙ per-type pony_msg_t, genfun.c):
    # the drain reads [cap, w1_in, rows]; sends still emit the global
    # width (they may target any cohort — delivery narrows per target).
    w1_in = 1 + cohort.msg_words
    field_dtypes = {}
    for fname, spec in cohort.atype.field_specs.items():
        field_dtypes[fname] = (jnp.float32 if spec is pack.F32
                               else jnp.int32)
    spawn_sites = tuple(sorted(cohort.spawns.items()))
    # Field specs of every spawn-target type, for synchronous
    # construction (Context.spawn_sync).
    spawn_meta = {t: program.by_type_name(t).atype.field_specs
                  for t, _ in spawn_sites}
    effects = {"destroy": False, "error": False, "sync_init": False}
    # Device blob pool (≙ actor-heap message payloads; see ops.pack.Blob):
    # a cohort that allocates (MAX_BLOBS) or receives/holds Blob handles
    # threads the pool arrays through its dispatch; everything else keeps
    # the blob-free structure (and fused-kernel eligibility) untouched.
    use_blob = opts.blob_slots > 0 and cohort.uses_blobs

    def _zero_inits():
        """Zero sync-init structure — shared by the fused busy path and
        idle_fn so the lax.cond branch pytrees can never drift."""
        return tuple(
            (jnp.zeros((batch * n * rows,), jnp.bool_),
             {f: jnp.zeros((batch * n * rows,),
                           jnp.float32 if sp is pack.F32 else jnp.int32)
              for f, sp in spawn_meta[tname].items()})
            for tname, n in spawn_sites)
    branches = [_make_branch(b, msg_words, ms, field_dtypes,
                             cohort.atype.field_specs, spawn_sites,
                             spawn_meta, effects, rows)
                for b in cohort.behaviours]
    nb = len(cohort.behaviours)
    base = cohort.behaviours[0].global_id if nb else 0
    sd = cohort.spawn_dispatches
    fused = None
    if opts.pallas_fused and nb >= 1:
        from ..ops import fused_dispatch as fd
        from ..ops import mailbox_kernel as mk
        # Probe-trace every branch so `effects` is discovered BEFORE
        # the kernel is built (it hosts destroy/error/spawn claims as
        # lane planes but cannot host sync-construction packaging).
        if not use_blob:
            for br in branches:
                jax.eval_shape(
                    br,
                    {f: jax.ShapeDtypeStruct((rows,), field_dtypes[f])
                     for f in cohort.atype.field_specs},
                    jax.ShapeDtypeStruct((cohort.msg_words, rows),
                                         jnp.int32),
                    jax.ShapeDtypeStruct((rows,), jnp.int32),
                    {t: jax.ShapeDtypeStruct((n, rows), jnp.int32)
                     for t, n in spawn_sites})
        fnames = tuple(cohort.atype.field_specs.keys())
        fused = (fd.build_fused_dispatch(
            cohort.behaviours, base_gid=base,
            field_names=fnames, field_dtypes=field_dtypes,
            field_specs=cohort.atype.field_specs, batch=batch,
            cap=cap, msg_words=msg_words,
            msg_words_in=cohort.msg_words, ms=ms, rows=rows,
            noyield=noyield, interpret=mk.interpret_mode(),
            spawn_sites=spawn_sites, spawn_meta=spawn_meta,
            spawn_dispatches=sd),
            fnames)

    def run_cohort(type_state_rows, buf_rows, head_rows, occ_rows,
                   runnable_rows, ids, resv, blob=None):
        # buf_rows: [cap, w1, rows]; resv: {target: [sd, sites, rows]};
        # blob (pool-using cohorts only): dict(data [W*B] flat,
        # word-major (state.pool_index), used [B], len [B], gen [B],
        # base i32, resv [blob_dispatches, sites, rows] global handles).
        e = rows * batch * ms
        if use_blob and blob is None:
            raise RuntimeError(
                f"cohort {cohort.atype.__name__} uses the blob pool but "
                "run_cohort got blob=None (engine wiring)")

        def scan_body(carry, x):
            (st, stopped, ef, ec, sfail, dstr, errf, errc, errl, used,
             nproc, nbad, blb, bused_c) = carry
            msg, valid = x                    # msg [w1, rows], valid [rows]
            # Blob reservation window for this dispatch: a used-counter
            # walk over the [blob_dispatches, sites, rows] windows — only
            # dispatches that actually allocate consume one (the
            # spawn_dispatches pattern; exhausted budget yields -1 refs
            # -> sticky blob_fail, never a double claim).
            rblob = None
            rblob_over = None
            if blb is not None:
                rt_b = blob["resv"]
                rblob = jnp.full(rt_b.shape[1:], -1, jnp.int32)
                for d in range(rt_b.shape[0]):
                    rblob = jnp.where((bused_c == d)[None, :], rt_b[d],
                                      rblob)
                # Lanes whose window was withheld for BUDGET (allocating
                # dispatch count past BLOB_DISPATCHES) — an alloc failure
                # there blames the budget knob, not the pool size.
                rblob_over = bused_c >= rt_b.shape[0]
            # Hand one dispatch-worth of spawn reservations to this batch
            # slot: a `used` counter walks the SPAWN_DISPATCHES axis;
            # exhausted budget yields -1 refs (→ sticky spawn_fail,
            # never a double claim).
            resv_k = {}
            for t, n_sites in spawn_sites:
                rt_ = resv[t]                 # [sd, sites, rows]
                sel = jnp.full((n_sites, rows), -1, jnp.int32)
                for d in range(sd):
                    sel = jnp.where((used == d)[None, :], rt_[d], sel)
                resv_k[t] = sel
            local = msg[0] - base
            in_range = (local >= 0) & (local < nb)
            do = valid & ~stopped
            # Planar dispatch: evaluate every behaviour on all lanes and
            # select per lane by behaviour id (what a vmapped lax.switch
            # executes, without the actor-major materialisations).
            st_n = dict(st)
            tgt_n = [jnp.full((rows,), -1, jnp.int32) for _ in range(ms)]
            wrd_n = [jnp.zeros((w1, rows), jnp.int32) for _ in range(ms)]
            ef_n = jnp.zeros((rows,), jnp.bool_)
            ec_n = jnp.zeros((rows,), jnp.int32)
            yf_n = jnp.zeros((rows,), jnp.bool_)
            sf_n = jnp.zeros((rows,), jnp.bool_)
            ds_n = jnp.zeros((rows,), jnp.bool_)
            erf_n = jnp.zeros((rows,), jnp.bool_)
            erc_n = jnp.zeros((rows,), jnp.int32)
            erl_n = jnp.zeros((rows,), jnp.int32)
            clm_n = [[jnp.full((rows,), -1, jnp.int32)
                      for _ in range(n)] for _, n in spawn_sites]
            ini_n = []
            for tname, n in spawn_sites:
                t_specs = spawn_meta[tname]
                t_dt = {f: (jnp.float32 if sp is pack.F32 else jnp.int32)
                        for f, sp in t_specs.items()}
                ini_n.append((
                    [jnp.zeros((rows,), jnp.bool_) for _ in range(n)],
                    {f: [jnp.full((rows,),
                                  pack.null_word(sp), t_dt[f])
                         for _ in range(n)]
                     for f, sp in t_specs.items()}))
            def _merge(br, take, acc):
                """Evaluate one behaviour planar and select its outputs
                where the slot's message id matches. Blob pool arrays
                thread SEQUENTIALLY (no select): branch take-masks are
                disjoint and every blob op is already take-masked inside
                the branch (api.BlobPoolView)."""
                (st_a, tgt_a, wrd_a, ef_a, ec_a, yf_a, sf_a, ds_a,
                 erf_a, erc_a, erl_a, clm_a, ini_a, blb_a) = acc
                blob_in = None
                if blb_a is not None:
                    blob_in = (blb_a[0], blb_a[1], blb_a[2], blb_a[3],
                               blob["base"], rblob, rblob_over)
                (st2, (btgt, bwrd), (bef, bec), byf, bclm, bini, bsf,
                 bds, (berf, berc, berl), bl_o) = br(
                    st, msg[1:], ids, resv_k, blob_in, take)
                if blb_a is not None:
                    blb_o = (bl_o[0], bl_o[1], bl_o[2], bl_o[3],
                             blb_a[4] | bl_o[4], blb_a[5] | bl_o[5],
                             blb_a[6] + bl_o[6], blb_a[7] + bl_o[7],
                             blb_a[8] + bl_o[8], blb_a[9] | bl_o[9])
                else:
                    blb_o = None
                st_o = {k: jnp.where(take, st2[k], st_a[k]) for k in st_a}
                tgt_o = [jnp.where(take, btgt[m], tgt_a[m])
                         for m in range(ms)]
                wrd_o = [jnp.where(take[None, :], bwrd[m], wrd_a[m])
                         for m in range(ms)]
                clm_o = [[jnp.where(take, bclm[si][s], clm_a[si][s])
                          for s in range(len(clm_a[si]))]
                         for si in range(len(spawn_sites))]
                ini_o = []
                for si in range(len(spawn_sites)):
                    bh, bv = bini[si]
                    hh, vv = ini_a[si]
                    ini_o.append((
                        [jnp.where(take, bh[s], hh[s])
                         for s in range(len(hh))],
                        {f: [jnp.where(take, bv[f][s], vv[f][s])
                             for s in range(len(vv[f]))] for f in vv}))
                return (st_o, tgt_o, wrd_o,
                        jnp.where(take, bef, ef_a),
                        jnp.where(take, bec, ec_a),
                        jnp.where(take, byf, yf_a),
                        jnp.where(take, bsf, sf_a),
                        jnp.where(take, bds, ds_a),
                        jnp.where(take, berf, erf_a),
                        jnp.where(take, berc, erc_a),
                        jnp.where(take, berl, erl_a),
                        clm_o, ini_o, blb_o)

            blb_acc = (blb + (jnp.zeros((rows,), jnp.bool_),)
                       if blb is not None else None)
            acc = (st_n, tgt_n, wrd_n, ef_n, ec_n, yf_n, sf_n, ds_n,
                   erf_n, erc_n, erl_n, clm_n, ini_n, blb_acc)
            for j, br in enumerate(branches):
                take = (do & in_range & (local == j))
                acc = _merge(br, take, acc)
            (st_n, tgt_n, wrd_n, ef_n, ec_n, yf_n, sf_n, ds_n,
             erf_n, erc_n, erl_n, clm_n, ini_n, blb_acc) = acc
            if blb_acc is not None:
                blb = blb_acc[:9]
                bused_c = bused_c + blb_acc[9].astype(jnp.int32)
            spawned_here = sf_n
            for si in range(len(spawn_sites)):
                for s in range(len(clm_n[si])):
                    spawned_here = spawned_here | (clm_n[si][s] >= 0)
            new_ef = ef | ef_n
            new_ec = jnp.where(ef_n & ~ef, ec_n, ec)
            stopped2 = stopped if noyield else (stopped | yf_n)
            stgt = jnp.stack(tgt_n) if ms else jnp.zeros((0, rows),
                                                         jnp.int32)
            swrd = jnp.stack(wrd_n) if ms else jnp.zeros((0, w1, rows),
                                                         jnp.int32)
            claims = tuple(
                (jnp.stack(c) if c else jnp.zeros((0, rows), jnp.int32))
                for c in clm_n)
            inits = tuple(
                ((jnp.stack(hh) if hh else jnp.zeros((0, rows), jnp.bool_)),
                 {f: (jnp.stack(vs) if vs
                      else jnp.zeros((0, rows), jnp.int32))
                  for f, vs in vv.items()})
                for hh, vv in ini_n)
            return ((st_n, stopped2, new_ef, new_ec, sfail | sf_n,
                     dstr | ds_n, errf | erf_n,
                     jnp.where(erf_n, erc_n, errc),
                     jnp.where(erf_n, erl_n, errl),
                     used + spawned_here.astype(jnp.int32),
                     nproc + (do & in_range).astype(jnp.int32),
                     nbad + (do & ~in_range).astype(jnp.int32), blb,
                     bused_c),
                    (stgt, swrd, do, claims, inits))

        def busy_fn(_):
            n_run = jnp.where(runnable_rows,
                              jnp.minimum(occ_rows, batch), 0)
            if fused is not None:
                kernel_fn, fnames = fused
                fields = tuple(type_state_rows[f] for f in fnames)
                resv_in = tuple(resv[t].reshape(sd * n, rows)
                                for t, n in spawn_sites)
                (nf_out, out_tgt, out_words, new_head, nproc_l, nbad_l,
                 ef_l, ec_l, ds_l, erf_l, erc_l, erl_l, claims_out,
                 sf_l) = kernel_fn(
                    fields, buf_rows, head_rows, n_run, ids, resv_in)
                stf = dict(zip(fnames, nf_out))
                any_exit = jnp.any(ef_l)
                code = ec_l[jnp.argmax(ef_l)]
                # Claims flatten (k, site, lane) exactly like the XLA
                # scan's stack; inits are the zero structure (the fused
                # path never hosts sync-construction — eligibility).
                claims_t = tuple(c.reshape(-1) for c in claims_out)
                return (stf, out_tgt, out_words, new_head, any_exit,
                        code, jnp.sum(nproc_l), jnp.sum(nbad_l),
                        claims_t, _zero_inits(), jnp.any(sf_l), ds_l,
                        erf_l, erc_l, erl_l, None)
            if opts.pallas:          # gate BEFORE importing pallas/mosaic
                from ..ops import mailbox_kernel as mk
                with phase_scope("drain"):
                    msgs, valids = mk.drain_msgs(
                        buf_rows, head_rows, n_run, batch=batch,
                        interpret=mk.interpret_mode())
            else:
                with phase_scope("drain"):
                    msgs = jnp.stack(
                        [_ring_take(buf_rows, (head_rows + k) % cap)
                         for k in range(batch)])        # [batch, w1, rows]
                    valids = (jnp.arange(batch, dtype=jnp.int32)[:, None]
                              < n_run[None, :])         # [batch, rows]
            z = lambda d: jnp.zeros((rows,), d)         # noqa: E731
            if use_blob:
                blb0 = (blob["data"], blob["used"], blob["len"],
                        blob["gen"], jnp.bool_(False), jnp.bool_(False),
                        jnp.int32(0), jnp.int32(0), jnp.int32(0))
            else:
                blb0 = None
            carry0 = (type_state_rows, z(jnp.bool_), z(jnp.bool_),
                      z(jnp.int32), z(jnp.bool_), z(jnp.bool_),
                      z(jnp.bool_), z(jnp.int32), z(jnp.int32),
                      z(jnp.int32), z(jnp.int32), z(jnp.int32), blb0,
                      z(jnp.int32))
            ((stf, _, ef, ec, sfail, dstr, errf, errc, errl, _used, nproc,
              nbad, blbf, _bused),
             (stgt, swrd, consumed, claims, inits)) = lax.scan(
                scan_body, carry0, (msgs, valids))
            # stgt [batch, ms, rows] → flat [e] with rows minor;
            # swrd [batch, ms, w1, rows] → [w1, e] planar.
            n_consumed = jnp.sum(consumed.astype(jnp.int32), axis=0)
            out_tgt = stgt.reshape(e)
            out_words = jnp.moveaxis(swrd, 2, 0).reshape(w1, e)
            any_exit = jnp.any(ef)
            code = ec[jnp.argmax(ef)]
            return (stf, out_tgt, out_words, head_rows + n_consumed,
                    any_exit, code, jnp.sum(nproc), jnp.sum(nbad),
                    tuple(c.reshape(-1) for c in claims),
                    tuple((h.reshape(-1),
                           {f: v.reshape(-1) for f, v in vals.items()})
                          for h, vals in inits),
                    jnp.any(sfail), dstr, errf, errc, errl, blbf)

        def idle_fn(_):
            # ≙ the fork's whole point (README.md:8-10, scaling_sleep): a
            # scheduler with no work must cost ~nothing. A cohort with no
            # queued runnable messages skips gather/dispatch/outbox
            # entirely — one reduction decides.
            blb_idle = ((blob["data"], blob["used"], blob["len"],
                         blob["gen"], jnp.bool_(False), jnp.bool_(False),
                         jnp.int32(0), jnp.int32(0), jnp.int32(0))
                        if use_blob else None)
            return (type_state_rows,
                    jnp.full((e,), -1, jnp.int32),
                    jnp.zeros((w1, e), jnp.int32),
                    head_rows, jnp.bool_(False), jnp.int32(0),
                    jnp.int32(0), jnp.int32(0),
                    tuple(jnp.full((batch * n * rows,), -1, jnp.int32)
                          for _, n in spawn_sites),
                    _zero_inits(),
                    jnp.bool_(False),
                    jnp.zeros((rows,), jnp.bool_),
                    jnp.zeros((rows,), jnp.bool_),
                    jnp.zeros((rows,), jnp.int32),
                    jnp.zeros((rows,), jnp.int32), blb_idle)

        busy = jnp.any(runnable_rows & (occ_rows > 0))
        # (cond traces both branches here, so `effects` is fully
        # populated by the time the lines below read it.)
        (stf, out_tgt, out_words, new_head, any_exit, code, nproc, nbad,
         claims_t, inits_t, sfail, dstr, errf, errc, errl,
         blob_out) = lax.cond(
            busy, busy_fn, idle_fn, operand=None)
        sender = jnp.tile(ids, batch * ms)    # entry (b, m, r): sender=ids[r]
        out = Entries(tgt=out_tgt, sender=sender, words=out_words)
        flat_claims = {t: c for (t, _), c in zip(spawn_sites, claims_t)}
        flat_inits = {t: i for (t, _), i in zip(spawn_sites, inits_t)}
        return (stf, out, new_head, any_exit, code, nproc, nbad,
                flat_claims,
                flat_inits if effects["sync_init"] else None,
                sfail,
                dstr if effects["destroy"] else None,
                (errf, errc, errl) if effects["error"] else None,
                blob_out)

    return run_cohort


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
    beside the long one (build_step): only where the received buckets
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
    caller's to check (build_step's `fits`)."""
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
           mute_slots: int, pressured_global, pressured_local,
           blob=None):
    """Mesh routing: pack entries into per-destination-shard buckets
    (`_route_pack`: one payload-carrying sort, then a contiguous slice a
    destination) and exchange them with three all_to_all over the actor
    axis (ICI): targets, senders, words.

    Returns (received Entries [shards*bucket], new route-spill, spill count,
    overflow flag, newly muted [n_local], their refs, ref overflow, blob
    results or None, (entries shipped, those of them off-shard)). Its
    parts carry the scopes `pony/route/sort`, `/bucket`, `/exchange` and
    `/spill` (state.STEP_SCOPES).
    Bucket overflow keeps messages on the source shard (route-spill,
    retried first next step) and mutes the sender — backpressure across
    the mesh without any receiver-side state (≙ the intent of
    ponyint_maybe_mute; the occupancy signal here is "the link to that
    shard is saturated").

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
        # bucket's slices and `pressured_global[ts]` loses its fast
        # memory, 114 ms for 68 at 8.4M entries (PERF.md §6, PR 41).
        ts, ss, ws, dt = lax.optimization_barrier((ts, ss, ws, dt))
        spilled = _route_spill(
            ts, ss, ws, dt, seg_start, cnt - acc, shards=shards,
            n_local=n_local, bucket=bucket, rspill_cap=rspill_cap,
            overload_occ=overload_occ, head=head, tail=tail,
            shard_base=shard_base, mute_slots=mute_slots,
            pressured_global=pressured_global,
            pressured_local=pressured_local)
    received = Entries(tgt=rt, sender=rs, words=rw)
    return (received, *spilled, blob_out, (n_routed, n_remote))


def _route_spill(ts, ss, ws, dt, seg_start, over, *, shards: int,
                 n_local: int, bucket: int, rspill_cap: int, overload_occ,
                 head, tail, shard_base, mute_slots: int, pressured_global,
                 pressured_local):
    """What did not fit its bucket, and who mutes for it: the sorted
    entries (`ts`, `ss`, `ws` by destination `dt`), each destination's
    `seg_start` and overflow `over` → (new route-spill, spill count,
    overflow flag, newly muted [n_local], their refs, ref overflow)."""
    e = ts.shape[0]
    nrej = jnp.sum(over)
    w1 = ws.shape[0]
    # Sends whose (possibly remote) target DECLARED pressure: the
    # cross-shard face of pony_apply_backpressure — every shard sees the
    # all-gathered pressured bits, so senders mute at routing time, not
    # only on the receiver's shard (≙ the reference muting any scheduler
    # that sends to an under-pressure actor).
    pr_t = (ts >= 0) & jnp.take(
        pressured_global, jnp.maximum(ts, 0), mode="clip")

    def pressure(_):
        # Bucket overflow → route spill (stays on this shard, ordered)
        # + mute the (always local) senders of parked or
        # pressured-targeted messages.
        rank = jnp.arange(e, dtype=jnp.int32) - seg_start[
            jnp.minimum(dt, shards - 1)]
        rej = (dt < shards) & (rank >= bucket)
        perm2, vsp, _ = compact_mask(rej, rspill_cap)
        spill = Entries(
            tgt=jnp.where(vsp, ts[perm2], -1),
            sender=jnp.where(vsp, ss[perm2], -1),
            words=jnp.where(vsp[None, :], ws[:, perm2], 0),
        )
        lsnd = ss - shard_base
        s_ok = (rej | pr_t) & (lsnd >= 0) & (lsnd < n_local)
        sc = jnp.minimum(jnp.maximum(lsnd, 0), n_local - 1)
        s_hot = (tail[sc] - head[sc]) > overload_occ
        # ≙ the reference's !OVERLOADED/UNDER_PRESSURE sender exemption
        # (actor.c mute rules): a sender that is itself hot or has
        # itself declared pressure never mutes — prevents two
        # host-pressured actors that message each other from
        # mutually muting into a stall.
        trig = s_ok & ~s_hot & ~pressured_local[sc]
        mute_row = jnp.where(trig, sc, n_local)
        newly_muted = jnp.zeros((n_local,), jnp.bool_).at[mute_row].max(
            trig, mode="drop")
        refs, ovf = mute_ref_slots(trig, mute_row, ts, n=n_local,
                                   k=mute_slots)
        return spill, newly_muted, refs, ovf

    def quiet(_):
        refs, ovf = empty_mute_slots(n_local, mute_slots)
        return (Entries(tgt=jnp.full((rspill_cap,), -1, jnp.int32),
                        sender=jnp.full((rspill_cap,), -1, jnp.int32),
                        words=jnp.zeros((w1, rspill_cap), jnp.int32)),
                jnp.zeros((n_local,), jnp.bool_), refs, ovf)

    new_rspill, newly_muted, new_refs, new_ovf = lax.cond(
        (nrej > 0) | jnp.any(pr_t), pressure, quiet, operand=None)
    return (new_rspill, jnp.minimum(nrej, rspill_cap), nrej > rspill_cap,
            newly_muted, new_refs, new_ovf)


# A row's status word for the unmute pass (`muter_bits` in the tick).
LIVE_CONG, CAN_RECOVER, RECOVERED, PRESSURED = 1, 2, 4, 8


def build_step(program: Program, opts: RuntimeOptions):
    """Trace one whole-world scheduler tick; returns a function
    local_step(state, inject_tgt, inject_words) → (state, StepAux) in
    *per-shard* coordinates. Wrap with jit (P=1) or shard_map (P>1) via
    jit_step()."""
    assert program.frozen
    check_kernels(program, opts)
    p = program.shards
    nl = program.n_local
    c = opts.mailbox_cap
    fh = program.first_host_row
    s_cap = opts.spill_cap
    tracing = opts.tracing   # static: causal trace lanes (PROFILE §10)
    dev_cohorts = program.device_cohorts
    dispatchers = [(_cohort_dispatch(ch, opts, opts.noyield, program), ch)
                   for ch in dev_cohorts]
    # Blob migration over the mesh: active iff some behaviour ROUTES a
    # Blob argument (static mask) and the pool is live (see _route).
    route_blobs = False
    if opts.blob_slots > 0 and p > 1:
        from .gc import build_blob_arg_mask
        _blob_route_mask = build_blob_arg_mask(program, opts.msg_words)
        # Iso-mode positions MOVE (source slot freed); val-mode (frozen,
        # shared) positions COPY — other readers keep the source.
        _blob_route_mask_iso = build_blob_arg_mask(
            program, opts.msg_words, mode="iso")
        route_blobs = bool(_blob_route_mask.any())
    e_out, bucket, _n_entries = layout_sizes(program, opts)
    # What one shard can emit a tick (its route spill and its outbox) is
    # what a balanced world hands it back: the length of the short
    # delivery list's routed part (step 4 of the tick).
    l_in = s_cap + e_out
    short_list = _unpack_fits(p, bucket, l_in)
    e_short = s_cap + opts.inject_slots + l_in
    # Delivery priority levels (see delivery.deliver): 0 = receiver
    # spill, 1 = host inject, 2+k = sender cohort with k-th highest
    # PRIORITY (≙ the fork's actor priority hint ordering contenders).
    import numpy as _np
    pri_sorted = sorted({ch.priority for ch in dev_cohorts}, reverse=True)
    pri_rank = {pv: i for i, pv in enumerate(pri_sorted)}
    n_levels = 2 + max(1, len(pri_sorted))
    # Per-cohort mailbox widths tiling the local row space (ALL cohorts,
    # device + host) — delivery rebuilds each table at its own width.
    cohort_layout = tuple(
        (ch.atype.__name__, ch.local_start, ch.local_stop,
         1 + ch.msg_words) for ch in program.cohorts)

    def spawning(ch):
        """Which behaviours of a spawner cohort hold a spawn site, by
        local behaviour index (verify's probe trace, at the window's
        trace like every other check of a behaviour's body): only a row
        about to dispatch one of them can be refused a row
        (StepAux.spawn)."""
        from .. import verify
        return _np.array([bool(verify.behaviour_effects(
            b, ch.atype, msg_words=opts.msg_words,
            default_max_sends=opts.max_sends).spawns)
            for b in ch.behaviours])

    def local_step(st: RtState, inject_tgt, inject_words
                   ) -> Tuple[RtState, StepAux]:
        with PhaseCursor() as phase:
            return tick(st, inject_tgt, inject_words, phase)

    def tick(st: RtState, inject_tgt, inject_words, phase: PhaseCursor
             ) -> Tuple[RtState, StepAux]:
        # `phase(name)` opens the named scope `pony/<name>` for what is
        # traced from there to the next call (state.PhaseCursor).
        phase("unmute")
        if p > 1:
            shard = lax.axis_index("actors").astype(jnp.int32)
        else:
            shard = jnp.int32(0)
        base = shard * nl

        def local_rows(entries):
            """Global target ids -> this shard's rows."""
            return entries._replace(tgt=jnp.where(
                entries.tgt >= 0, entries.tgt - base, -1))
        occ0 = st.tail - st.head
        # World bits (previous tick's mesh-wide vote, stored replicated
        # per shard): bit0 = any actor pressured anywhere, bit1 = any
        # muted anywhere, bit2 = any route-spill entries anywhere. They
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
        # Mesh-wide pressured bits (≙ pony_apply_backpressure being
        # visible to every scheduler): one all_gather of the [nl] bool
        # column — it lets BOTH the routing mute and the remote unmute
        # guard see off-shard pressure. Gated: ticks on a mesh with no
        # declared pressure anywhere skip the gather (zeros are exact).
        if p > 1:
            pressured_global = lax.cond(
                world_pressured,
                lambda _: lax.all_gather(st.pressured, "actors",
                                         tiled=True),
                lambda _: jnp.zeros((p * nl,), jnp.bool_),
                operand=None)
        else:
            pressured_global = st.pressured

        # --- 1. unmute pass (≙ ponyint_sched_unmute_senders,
        # scheduler.c:1552-1635: receiver recovered → senders released).
        # The per-row pending histogram (a scatter-add, which serialises
        # on TPU) only runs when the spill actually holds messages — the
        # steady state skips it entirely.
        dspill_pending = lax.cond(
            st.dspill_count[0] > 0,
            lambda _: counts_by_key(
                jnp.minimum(jnp.maximum(st.dspill_tgt, 0), nl - 1),
                (st.dspill_tgt >= 0).astype(jnp.int32), nl),
            lambda _: jnp.zeros((nl,), jnp.int32), operand=None)
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
        live_cong = (((occ0 > opts.unmute_occ) | (dspill_pending > 0))
                     & can_recover)
        recovered = ((occ0 <= opts.unmute_occ) & (dspill_pending == 0)
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
                world_muted,
                lambda _: lax.all_gather(muter_bits, "actors",
                                         tiled=True),
                lambda _: jnp.zeros((p * nl,), jnp.int32),
                operand=None)
        else:
            muter_bits_global = muter_bits

        def unmute_pass(_):
            # ≙ ponyint_sched_unmute_senders walking the mutemap
            # receiver-set (scheduler.c:1552-1635): a sender releases only
            # when EVERY tracked muting receiver has recovered.
            refs = st.mute_refs                       # [K, nl]
            has = refs >= 0
            lref = refs - base
            ref_local = (lref >= 0) & (lref < nl)
            status = muter_bits_global
            if p > 1:
                # Each bit is believed from where it was believed before
                # the word: live-congested and can-recover as gathered
                # under world bit1, pressure from its own all-gather
                # (world bit0), and `recovered` from this shard's rows
                # alone — a remote ref's is never read.
                status = ((status & (LIVE_CONG | CAN_RECOVER))
                          | jnp.where(pressured_global, PRESSURED, 0)
                          | lax.dynamic_update_slice(
                              jnp.zeros((p * nl,), jnp.int32),
                              muter_bits & RECOVERED, (base,)))
            got = jnp.take(status, jnp.maximum(refs, 0), mode="clip")

            def says(bit):       # [K, nl]: the ref's muter has `bit` set
                return has & ((got & bit) > 0)
            ref_pressured = says(PRESSURED)
            local_ok = ref_local & says(RECOVERED)
            # Remote muting ref: release once this shard's route-spill
            # drained (the local evidence of congestion is gone;
            # receiver-side pressure will re-mute via routing if it
            # persists) — unless the remote receiver still DECLARES
            # pressure (the all-gathered bits above), which holds the
            # sender muted exactly as a local pressured ref would.
            remote_ok = (has & ~ref_local & (st.rspill_count[0] == 0)
                         & ~ref_pressured)
            slot_ok = ~has | local_ok | remote_ok
            all_ok = jnp.all(slot_ok, axis=0)
            # Overflowed ref sets (more distinct muters than slots) defer
            # to a shard-wide quiet condition — conservative, never early.
            # Overflowed ref sets may have EVICTED a pressured ref
            # (slot collision), so the conservative release condition
            # consults the whole world's pressure bits, not just local.
            shard_quiet = (jnp.max(occ0) <= opts.unmute_occ) \
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
        muted, mute_refs, mute_ovf = lax.cond(
            jnp.any(st.muted), unmute_pass,
            lambda _: (st.muted, st.mute_refs, st.mute_ovf), operand=None)

        phase("spawn")
        # --- 1b. spawn reservations (≙ pony_create's slot allocation,
        # actor.c:688-734, done ahead of dispatch): per spawn-target
        # cohort, compact this shard's free rows (dead, drained, no stale
        # spill) and hand each spawner cohort its statically-partitioned
        # window, reshaped to per-(actor, batch-slot, site) refs.
        free_rows: Dict[str, jnp.ndarray] = {}

        def rspill_hits(rspill_tgt, any_rspill):
            """[nl] bool: rows some shard's route-spill still addresses.
            A message parked in *another shard's* route-spill may be
            addressed to a locally dead row; reclaiming that row would
            deliver the stale message to the newborn. One psum over the
            mesh makes every shard's rspill targets globally visible —
            the cross-shard twin of the dspill_pending guard. Gated on
            world bit2: with every shard's route-spill empty (the
            steady state) the psum is skipped and zeros are exact."""
            if not (program.spawn_target_names and p > 1):
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

        rspill_hit = rspill_hits(st.rspill_tgt, world_rspill)
        for tname in program.spawn_target_names:
            tc = program.by_type_name(tname)
            with phase_scope("spawn/free"):
                perm, vfree, _ = compact_mask(
                    free_mask(tc, st.alive, occ0, dspill_pending,
                              rspill_hit), tc.local_capacity)
                free_rows[tname] = jnp.where(
                    vfree, tc.local_start + perm.astype(jnp.int32),
                    jnp.int32(-1))

        # --- 2. drain + dispatch per cohort (≙ actor run loop).
        runnable = st.alive & ~muted

        def cohort_resv(ch):
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
                run_c = runnable[s0:s1] & (occ0[s0:s1] > 0)
                rank = jnp.cumsum(run_c.astype(jnp.int32)) - 1
                sd = ch.spawn_dispatches
                for tname, sites in sorted(ch.spawns.items()):
                    per = sd * sites
                    off = ch.spawn_offsets[tname]
                    widx = jnp.where(run_c, rank * per, 0)
                    # Planar [sd, sites, rows]: the per-(dispatch, site)
                    # offsets are the small major axes, actor lanes
                    # minor.
                    idx = (off + widx[None, None, :]
                           + (jnp.arange(sd, dtype=jnp.int32)
                              * sites)[:, None, None]
                           + jnp.arange(sites,
                                        dtype=jnp.int32)[None, :, None])
                    rows = jnp.take(free_rows[tname], idx, mode="fill",
                                    fill_value=-1)
                    resv[tname] = jnp.where(
                        (rows >= 0) & run_c[None, None, :],
                        base + rows, jnp.int32(-1))
            return resv

        # --- 2a'. device blob pool reservations (the spawn-reservation
        # pattern applied to the "actor heap": compact this shard's free
        # pool slots, hand each allocating cohort its statically-
        # partitioned window; ≙ pony_alloc on the owning actor's heap,
        # done race-free ahead of the planar dispatch).
        blob_en = opts.blob_slots > 0
        if blob_en:
            bsl = opts.blob_slots
            bbase = shard * bsl
            # Idle costs nothing (the fork's thesis, README.md:8-10):
            # the free-slot compaction feeds only reservation windows,
            # and no window is READ unless an allocating cohort
            # dispatches — so skip the sort when none has queued work.
            alloc_busy = jnp.bool_(False)
            for _ch in dev_cohorts:
                if _ch.blob_sites and _ch.blob_dispatches:
                    _sl = slice(_ch.local_start, _ch.local_stop)
                    alloc_busy = alloc_busy | jnp.any(
                        runnable[_sl] & (occ0[_sl] > 0))

            def _compact_free(_):
                bperm, bvfree, _n = compact_mask(~st.blob_used, bsl)
                return jnp.where(bvfree,
                                 bbase + bperm.astype(jnp.int32),
                                 jnp.int32(-1))
            free_blob = lax.cond(
                alloc_busy, _compact_free,
                lambda _: jnp.full((bsl,), -1, jnp.int32), operand=None)
        blob_cur = (st.blob_data, st.blob_used, st.blob_len, st.blob_gen)
        blob_fail = st.blob_fail[0]
        blob_budget = st.blob_budget_fail[0]
        nb_alloc = jnp.int32(0)
        nb_free = jnp.int32(0)
        nb_remote = jnp.int32(0)

        def cohort_blob_resv(ch):
            """[bd, sites, rows] reserved global blob handles: each
            runnable actor gets blob_dispatches×sites disjoint windows
            into the compacted free list (idle actors reserve nothing);
            a used-counter walk hands one window to each dispatch that
            actually allocates (the spawn_dispatches pattern)."""
            sites = ch.blob_sites
            bd = ch.blob_dispatches
            if not sites:
                return jnp.zeros((bd, 0, ch.local_capacity), jnp.int32)
            run_c = runnable[ch.local_start:ch.local_stop]
            rank = jnp.cumsum(run_c.astype(jnp.int32)) - 1
            per = bd * sites
            widx = jnp.where(run_c, rank * per, 0)
            idx = (ch.blob_offset + widx[None, None, :]
                   + (jnp.arange(bd, dtype=jnp.int32)
                      * sites)[:, None, None]
                   + jnp.arange(sites, dtype=jnp.int32)[None, :, None])
            handles = jnp.take(free_blob, idx, mode="fill", fill_value=-1)
            return jnp.where(run_c[None, None, :], handles, jnp.int32(-1))
        phase("dispatch")
        new_type_state: Dict[str, Dict[str, Any]] = dict(st.type_state)
        head_segments: List[jnp.ndarray] = []
        out_entries: List[Entries] = []
        claim_lists: Dict[str, List[jnp.ndarray]] = {
            t: [] for t in program.spawn_target_names}
        init_lists: Dict[str, List[Any]] = {
            t: [] for t in program.spawn_target_names}
        destroy_rows: List[Tuple[int, jnp.ndarray]] = []  # (s0, [rows] bool)
        error_rows: List[Tuple[int, Any]] = []   # (s0, ([rows] bool, codes))
        exit_f = st.exit_flag[0]
        exit_c = st.exit_code[0]
        spawn_fail = st.spawn_fail[0]
        nproc_total = jnp.int32(0)
        nbad_total = jnp.int32(0)
        drain_facts = []   # (cohort, head before, head after) — feeds
        #   the profiler lanes (profile_lanes) when analysis >= 1
        for run_cohort, ch in dispatchers:
            s0, s1 = ch.local_start, ch.local_stop
            ids = base + s0 + jnp.arange(ch.local_capacity, dtype=jnp.int32)
            with phase_scope("spawn"):
                resv = cohort_resv(ch)
                if blob_en and ch.uses_blobs:
                    blobd = {"data": blob_cur[0], "used": blob_cur[1],
                             "len": blob_cur[2], "gen": blob_cur[3],
                             "base": bbase, "resv": cohort_blob_resv(ch)}
                else:
                    blobd = None
            (stf, out, new_head_rows, ef, ec, nproc, nbad, claims, inits,
             sfail, dstr, errs, blob_out) = run_cohort(
                st.type_state[ch.atype.__name__],
                st.buf[ch.atype.__name__], st.head[s0:s1], occ0[s0:s1],
                runnable[s0:s1], ids, resv, blob=blobd)
            if blob_out is not None:
                blob_cur = blob_out[:4]
                blob_fail = blob_fail | blob_out[4]
                blob_budget = blob_budget | blob_out[5]
                nb_alloc = nb_alloc + blob_out[6]
                nb_free = nb_free + blob_out[7]
                nb_remote = nb_remote + blob_out[8]
            new_type_state[ch.atype.__name__] = stf
            head_segments.append(new_head_rows)
            if opts.analysis >= 1:
                drain_facts.append((ch, st.head[s0:s1], new_head_rows))
            out_entries.append(out)
            for t, cl in claims.items():
                claim_lists[t].append(cl)
                init_lists[t].append(None if inits is None else inits[t])
            if ch.spawns:
                spawn_fail = spawn_fail | sfail
            destroy_rows.append((s0, dstr))
            error_rows.append((s0, errs))
            exit_c = jnp.where(ef & ~exit_f, ec, exit_c)
            exit_f = exit_f | ef
            nproc_total = nproc_total + nproc
            nbad_total = nbad_total + nbad
        if fh < nl:  # host-cohort heads unchanged by device dispatch
            head_segments.append(st.head[fh:nl])
        new_head = (jnp.concatenate(head_segments) if head_segments
                    else st.head)

        phase("spawn")
        # --- 2b. apply spawn claims (before delivery, so constructor
        # messages and same-step sends to the newborn land): claimed rows
        # become alive with a fresh empty mailbox and zeroed state fields
        # (the constructor behaviour initialises them — Pony's `create` is
        # itself the first message).
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

        # --- 2c. causal-trace spans + context propagation (tracing on
        # only; the Python-level gate keeps the jaxpr bit-identical to
        # a tracer-free build otherwise — tests/test_tracing.py traps
        # trace_span_lanes to prove it). Every cohort's outbox gains
        # two trailing word rows carrying (trace_id, span_id) of the
        # dispatch that emitted each entry; spills, routing and
        # delivery move them with the payload from here on.
        if tracing:
            phase("analysis")
            (span_data2, span_count2, span_dropped2, span_next2,
             tr_rows) = trace_span_lanes(program, opts, st, drain_facts,
                                         base, shard)
            out_entries = [
                o._replace(words=jnp.concatenate([o.words, t], axis=0))
                for o, t in zip(out_entries, tr_rows)]

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
        if p > 1:
            rblob = None
            if route_blobs:
                rblob = {"data": blob_cur[0], "used": blob_cur[1],
                         "len": blob_cur[2], "gen": blob_cur[3],
                         "bbase": bbase, "bsl": bsl, "shard": shard,
                         "mask": _blob_route_mask,
                         "mask_iso": _blob_route_mask_iso}
            (incoming, new_rspill, rsp_count, rsp_over, route_muted,
             route_refs, route_ovf, route_blob_out, routed) = _route(
                out_cat, shards=p, n_local=nl, bucket=bucket,
                rspill_cap=s_cap, overload_occ=opts.overload_occ,
                head=new_head, tail=tail0, shard_base=base,
                mute_slots=opts.mute_slots,
                pressured_global=pressured_global,
                pressured_local=st.pressured, blob=rblob)
            if route_blob_out is not None:
                blob_cur, n_ship, n_recv, n_drop = route_blob_out
                nb_free = nb_free + n_ship
                nb_alloc = nb_alloc + n_recv
                nb_moved = n_recv
                nb_remote = nb_remote + n_drop
            else:
                nb_moved = jnp.int32(0)
            if not short_list:
                incoming = local_rows(incoming)
        else:
            incoming = local_rows(out_cat)
            new_rspill = Entries(st.rspill_tgt, st.rspill_sender,
                                 st.rspill_words)   # unused, stays empty
            rsp_count = st.rspill_count[0]
            rsp_over = jnp.bool_(False)
            nb_moved = jnp.int32(0)

        # --- 4. delivery list: receiver spill first (oldest), then host
        # injections, then routed messages. Injections are replicated to
        # all shards; each shard keeps only rows it owns.
        def delivery_list(incoming):
            """`incoming` (local rows) behind the receiver spill and the
            injections, and every entry's level."""
            inj_l = inject_tgt - base
            inj_local = jnp.where((inj_l >= 0) & (inj_l < nl), inj_l, -1)
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
            if len(pri_sorted) <= 1:
                lvl_in = jnp.full_like(incoming.tgt, 2)
            elif p == 1:
                lvl_in = jnp.concatenate(
                    [jnp.full_like(rspill_e.tgt, 2)]
                    + [jnp.full_like(o.tgt, 2 + pri_rank[ch.priority])
                       for ch, o in zip(dev_cohorts, out_entries)])
            else:
                prio_row = _np.zeros((nl,), _np.int32)
                for ch in dev_cohorts:
                    prio_row[ch.local_start:ch.local_stop] = \
                        pri_rank[ch.priority]
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

        def delivered(all_e, lvl_all, plan):
            return deliver(st.buf, new_head, tail0, alive, all_e,
                           n_local=nl, mailbox_cap=c, spill_cap=s_cap,
                           overload_occ=opts.overload_occ, shard_base=base,
                           cohort_layout=cohort_layout,
                           mute_slots=opts.mute_slots,
                           level=lvl_all, n_levels=n_levels, plan=plan,
                           pressured=st.pressured,
                           cosort=(opts.delivery == "cosort"),
                           trace_buf=st.trace_buf if tracing else None)

        plan = (st.plan_key, st.plan_perm, st.plan_bounds)
        n_unpacked = jnp.int32(0)
        if not short_list:
            all_e, lvl_all = delivery_list(incoming)
            phase("delivery")
            res = delivered(all_e, lvl_all, plan)
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
                    all_e, lvl_all = delivery_list(local_rows(incoming))
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
                # phase_cost_lanes counts the list's valid entries, and
                # the long list holds the same ones: built here for its
                # targets alone, the rest of it is dead code.
                all_e, _ = delivery_list(local_rows(incoming))

        phase("gc_mark")
        # --- 4b. apply destroys (≙ ponyint_actor_setpendingdestroy +
        # ponyint_actor_destroy, actor.c:570-664): the slot dies at end of
        # step; its remaining queue is discarded (head := tail), flags
        # clear, and the row becomes reclaimable by a later spawn.
        new_tail = res.tail
        pinned = st.pinned
        pressured = st.pressured
        # Int-coded error residue (≙ pony_error_int/code, fork): latest
        # nonzero code per actor + a counter; zero-cost for cohorts whose
        # behaviours never call ctx.error_int (gated at trace).
        last_error = st.last_error
        last_error_loc = st.last_error_loc
        n_errors = jnp.int32(0)
        for s0, errs in error_rows:
            if errs is None:
                continue
            errf, errc, errl = errs
            rows = jnp.where(errf, s0 + jnp.arange(errf.shape[0],
                                                   dtype=jnp.int32), nl)
            last_error = last_error.at[rows].set(
                jnp.where(errf, errc, 0), mode="drop")
            last_error_loc = last_error_loc.at[rows].set(
                jnp.where(errf, errl, 0), mode="drop")
            n_errors = n_errors + jnp.sum(errf.astype(jnp.int32))
        n_destroyed = jnp.int32(0)
        for s0, dstr in destroy_rows:
            if dstr is None:
                continue
            rows = jnp.where(dstr, s0 + jnp.arange(dstr.shape[0],
                                                   dtype=jnp.int32), nl)
            alive = alive.at[rows].set(False, mode="drop")
            new_head = new_head.at[rows].set(
                jnp.take(new_tail, jnp.minimum(rows, nl - 1)), mode="drop")
            muted = muted.at[rows].set(False, mode="drop")
            mute_refs = mute_refs.at[:, rows].set(-1, mode="drop")
            mute_ovf = mute_ovf.at[rows].set(False, mode="drop")
            pinned = pinned.at[rows].set(False, mode="drop")
            pressured = pressured.at[rows].set(False, mode="drop")
            n_destroyed = n_destroyed + jnp.sum(dstr.astype(jnp.int32))

        phase("mute")
        # --- 5. mute bookkeeping (≙ ponyint_mute_actor + mutemap insert,
        # actor.c:1171-1207, mutemap.c): this tick's muting refs from
        # delivery and routing MERGE into each sender's slot table (a
        # re-muted sender keeps its older muters); a slot collision
        # between distinct refs sets the sticky overflow bit.
        def _merge_slots(a, b):
            both = (a >= 0) & (b >= 0)
            m = jnp.where(a < 0, b, jnp.where(b < 0, a, jnp.maximum(a, b)))
            return m, jnp.any(both & (a != b), axis=0)

        newly = (res.newly_muted | route_muted) & alive
        became_muted = newly & ~muted
        muted2 = muted | newly
        # Consecutive-muted-tick counter (see the aging release above):
        # +1 while muted, reset on release or fresh mute.
        mute_age2 = jnp.where(muted2,
                              jnp.where(became_muted, 0,
                                        st.mute_age + 1),
                              0)

        def merge_mutes(_):
            inc_refs, c1 = _merge_slots(res.new_mute_refs, route_refs)
            merged_refs, c2 = _merge_slots(mute_refs, inc_refs)
            return (jnp.where(newly[None, :], merged_refs, mute_refs),
                    jnp.where(newly,
                              mute_ovf | res.new_mute_ovf | route_ovf
                              | c1 | c2,
                              mute_ovf))

        # The [K, N] slot-table merge only runs on ticks that actually
        # muted someone (≙ mutemap inserts happening only on mute).
        mute_refs2, mute_ovf2 = lax.cond(
            jnp.any(newly), merge_mutes,
            lambda _: (mute_refs, mute_ovf), operand=None)

        # --- 5b. per-event trace ring (analysis level 3 only; ≙ the
        # fork's per-event analysis rows, analysis.c:587-692): record the
        # tick's TRANSITIONS (mute, unmute, overload-on, spawn, destroy,
        # error) as (event, actor, step) triples compacted into a bounded
        # ring the host drains at window boundaries. Traced only when
        # enabled; and under a cond so event-free ticks skip the
        # compaction sort.
        occ_after = new_tail - new_head
        ev_data, ev_count, ev_dropped = (st.ev_data, st.ev_count[0],
                                         st.ev_dropped[0])
        if opts.analysis >= 1:
            phase("analysis")
        if opts.analysis >= 3:
            released_ev = st.muted & ~muted & alive
            over_ev = (occ_after > opts.overload_occ) \
                & ~(occ0 > opts.overload_occ)
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
                actors = base + jnp.tile(
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

            ev_data, ev_count, ev_dropped = lax.cond(
                jnp.any(masks), record,
                lambda _: (ev_data, ev_count, ev_dropped), operand=None)

        # --- 5c. per-behaviour profiler lanes (analysis level >= 1 only;
        # the gate is PYTHON-level, so level 0 traces none of this —
        # tests trap profile_lanes to assert exactly that).
        if opts.analysis >= 1:
            (beh_runs2, beh_del2, beh_rej2, coh_mt2, qw_hist2,
             qw_enq2) = profile_lanes(program, opts, st, tail0, res,
                                      drain_facts, muted2)
            phase_cost2 = phase_cost_lanes(
                st, all_e, drain_facts, nproc_total, n_spawned,
                n_destroyed, res.rebuild_slots)
        else:
            beh_runs2, beh_del2, beh_rej2 = (st.beh_runs,
                                             st.beh_delivered,
                                             st.beh_rejected)
            coh_mt2, qw_hist2 = st.coh_mute_ticks, st.qwait_hist
            qw_enq2 = dict(st.qwait_enq)
            phase_cost2 = st.phase_cost

        # --- 6. the vote: the tick's facts reduced to the aux the window's
        # continue test (aux_go) and the host read.
        phase("vote")
        nrej_new = st.n_rejected[0] + res.n_rejected
        nbad_new = st.n_badmsg[0] + nbad_total
        ndl_new = st.n_deadletter[0] + res.n_deadletter
        nmut_new = st.n_mutes[0] + jnp.sum(became_muted.astype(jnp.int32))
        if opts.analysis >= 1:
            occ_sum = jnp.sum(occ_after)
            occ_max = jnp.max(occ_after)
            n_muted_now = jnp.sum(muted2.astype(jnp.int32))
            n_over_now = jnp.sum(
                (occ_after > opts.overload_occ).astype(jnp.int32))
            nrej_all, nbad_all, ndl_all, nmut_all = (
                nrej_new, nbad_new, ndl_new, nmut_new)
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
        else:
            occ_sum = occ_max = n_muted_now = n_over_now = jnp.int32(0)
            nrej_all = nbad_all = ndl_all = nmut_all = jnp.int32(0)
            qw_p99 = jnp.int32(0)
        local_pending = (jnp.any(occ_after[:fh] > 0)
                         | (res.spill_count > 0) | (rsp_count > 0))
        any_muted_local = jnp.any(muted2)
        host_pending = (jnp.any(occ_after[fh:] > 0) if fh < nl
                        else jnp.bool_(False))
        # Sticky: once any step overflowed, every later aux reports it, so
        # the host catches it whatever its fetch cadence (quiesce_interval).
        overflow = st.spill_overflow[0] | res.spill_overflow | rsp_over
        # End-of-tick facts feeding the next tick's gather gates (exact,
        # not conservative: `pressured`/`muted2` are post-destroy finals,
        # `rsp_count` is the post-route spill count).
        any_pressured_local = jnp.any(pressured)
        any_rspill_local = rsp_count > 0
        if p > 1:
            # ONE packed psum + ONE packed pmax replace the former ~17
            # separate collectives (≙ the CNF/ACK token protocol being a
            # single token, not one message per fact, scheduler.c:303-480).
            # Booleans ride as 0/1 counts ("any" = sum > 0); cumulative
            # counters wrap mod 2^32 exactly as the per-shard counters do.
            i32c = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
            summed = lax.psum(jnp.stack([
                i32c(spawn_fail), i32c(local_pending),
                i32c(any_muted_local), i32c(host_pending),
                i32c(exit_f), i32c(overflow),
                i32c(any_pressured_local), i32c(any_rspill_local),
                st.n_processed[0] + nproc_total,
                st.n_delivered[0] + res.n_delivered,
                occ_sum, n_muted_now, n_over_now,
                nrej_all, nbad_all, ndl_all, nmut_all,
                i32c(blob_fail), i32c(blob_budget)]), "actors")
            spawn_fail_any = summed[0] > 0
            device_pending = summed[1] > 0
            any_muted_all = summed[2] > 0
            host_pending = summed[3] > 0
            exit_any = summed[4] > 0
            overflow_any = summed[5] > 0
            any_pressured_all = summed[6] > 0
            any_rspill_all = summed[7] > 0
            nproc_all = summed[8]
            ndel_all = summed[9]
            blob_fail_any = summed[17] > 0
            blob_budget_any = summed[18] > 0
            if opts.analysis >= 1:
                occ_sum, n_muted_now, n_over_now = (summed[10], summed[11],
                                                    summed[12])
                nrej_all, nbad_all, ndl_all, nmut_all = (
                    summed[13], summed[14], summed[15], summed[16])
            maxed = lax.pmax(jnp.stack([
                jnp.where(exit_f, exit_c, jnp.int32(-2**31)), occ_max,
                qw_p99]), "actors")
            exit_code_all = jnp.where(exit_any, maxed[0], exit_c)
            if opts.analysis >= 1:
                occ_max = maxed[1]
                qw_p99 = maxed[2]
        else:
            spawn_fail_any = spawn_fail
            device_pending = local_pending
            any_muted_all = any_muted_local
            exit_any = exit_f
            exit_code_all = exit_c
            overflow_any = overflow
            any_pressured_all = any_pressured_local
            any_rspill_all = any_rspill_local
            nproc_all = st.n_processed[0] + nproc_total
            ndel_all = st.n_delivered[0] + res.n_delivered
            blob_fail_any = blob_fail
            blob_budget_any = blob_budget
        # Row pressure (a program with device spawns only): what the
        # NEXT tick's reservations will find, read off this tick's final
        # state with the next tick's own predicates: the free rows by
        # free_mask; the rows that will reserve, those that hold a
        # message (muted or not: an unmute may release them first). A
        # row can be refused only when it dispatches a behaviour that
        # spawns, so a spawner cohort needs its window up to the LAST
        # such row: spawn_offset + (that row's rank among the reserving
        # rows + 1) × spawn_dispatches × sites.
        spawn_aux = {}
        if program.has_device_spawns:
            with phase_scope("spawn/reserve"):
                pending2 = lax.cond(
                    res.spill_count > 0,
                    lambda _: counts_by_key(
                        jnp.minimum(jnp.maximum(res.spill.tgt, 0), nl - 1),
                        (res.spill.tgt >= 0).astype(jnp.int32), nl),
                    lambda _: jnp.zeros((nl,), jnp.int32), operand=None)
                rhit2 = rspill_hits(new_rspill.tgt, any_rspill_all)
                n_free = {
                    t: jnp.sum(free_mask(
                        program.by_type_name(t), alive, occ_after, pending2,
                        rhit2).astype(jnp.int32))
                    for t in program.spawn_target_names}
                room = jnp.int32(2**31 - 1)
                for ch in dev_cohorts:
                    if not ch.spawns:
                        continue
                    s0, s1 = ch.local_start, ch.local_stop
                    holds = alive[s0:s1] & (occ_after[s0:s1] > 0)
                    may = spawning(ch)
                    wants = holds
                    if not may.all():
                        # some behaviour never spawns: ask the messages
                        # the next dispatch will take which they are
                        gid0 = ch.behaviours[0].global_id
                        gids = res.buf[ch.atype.__name__][:, :1, :]
                        wants = jnp.zeros_like(holds)
                        for k in range(ch.batch):
                            beh = _ring_take(
                                gids, (new_head[s0:s1] + k) % c)[0] - gid0
                            wants = wants | (
                                (k < occ_after[s0:s1])
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
            spawn_aux = {"room": room,
                         "low": jnp.where(room >= 0, room,
                                          jnp.int32(2**31 - 1)),
                         "spawned": born}
        wb_new =(any_pressured_all.astype(jnp.int32)
                  | (any_muted_all.astype(jnp.int32) << 1)
                  | (any_rspill_all.astype(jnp.int32) << 2))

        def vec(x, dtype=None):   # per-shard "scalar" → [1]
            return jnp.asarray(x, dtype).reshape(1)

        st2 = RtState(
            buf=res.buf, head=new_head, tail=new_tail,
            alive=alive, muted=muted2, mute_refs=mute_refs2,
            mute_age=mute_age2,
            mute_ovf=mute_ovf2, pinned=pinned, pressured=pressured,
            dspill_tgt=res.spill.tgt, dspill_sender=res.spill.sender,
            dspill_words=res.spill.words,
            dspill_count=vec(res.spill_count),
            rspill_tgt=new_rspill.tgt, rspill_sender=new_rspill.sender,
            rspill_words=new_rspill.words,
            rspill_count=vec(rsp_count),
            route_counts=({name: vec(st.route_counts[name][0] + n)
                           for name, n in zip(ROUTE_COUNTERS,
                                              (*routed, n_unpacked))}
                          if p > 1 else st.route_counts),
            spill_overflow=vec(overflow, jnp.bool_),
            exit_flag=vec(exit_f, jnp.bool_), exit_code=vec(exit_c),
            step_no=vec(st.step_no[0] + 1),
            n_processed=vec(st.n_processed[0] + nproc_total),
            n_delivered=vec(st.n_delivered[0] + res.n_delivered),
            n_rejected=vec(nrej_new),
            n_badmsg=vec(nbad_new),
            n_deadletter=vec(ndl_new),
            n_mutes=vec(nmut_new),
            n_spawned=vec(st.n_spawned[0] + n_spawned),
            n_destroyed=vec(st.n_destroyed[0] + n_destroyed),
            spawn_fail=vec(spawn_fail, jnp.bool_),
            n_collected=st.n_collected,
            last_error=last_error, last_error_loc=last_error_loc,
            n_errors=vec(st.n_errors[0] + n_errors),
            ev_data=ev_data, ev_count=vec(ev_count),
            ev_dropped=vec(ev_dropped),
            beh_runs=beh_runs2, beh_delivered=beh_del2,
            beh_rejected=beh_rej2, coh_mute_ticks=coh_mt2,
            qwait_hist=qw_hist2, qwait_enq=qw_enq2,
            phase_cost=phase_cost2,
            trace_buf=res.trace_buf,
            span_data=span_data2 if tracing else st.span_data,
            span_count=(vec(span_count2) if tracing else st.span_count),
            span_dropped=(vec(span_dropped2) if tracing
                          else st.span_dropped),
            span_next=(vec(span_next2) if tracing else st.span_next),
            plan_key=res.plan_key, plan_perm=res.plan_perm,
            plan_bounds=res.plan_bounds,
            world_bits=vec(wb_new),
            blob_data=blob_cur[0], blob_used=blob_cur[1],
            blob_len=blob_cur[2], blob_gen=blob_cur[3],
            blob_fail=vec(blob_fail, jnp.bool_),
            blob_budget_fail=vec(blob_budget, jnp.bool_),
            n_blob_alloc=vec(st.n_blob_alloc[0] + nb_alloc),
            n_blob_free=vec(st.n_blob_free[0] + nb_free),
            n_blob_remote=vec(st.n_blob_remote[0] + nb_remote),
            n_blob_moved=vec(st.n_blob_moved[0] + nb_moved),
            type_state=new_type_state,
        )
        aux = StepAux(
            device_pending=device_pending,
            host_pending=host_pending,
            any_muted=any_muted_all,
            exit_flag=exit_any, exit_code=exit_code_all,
            spill_overflow=overflow_any,
            spawn_fail=spawn_fail_any,
            blob_fail=blob_fail_any,
            blob_budget_fail=blob_budget_any,
            n_processed=nproc_all,
            n_delivered=ndel_all,
            occ_sum=occ_sum, occ_max=occ_max,
            n_muted_now=n_muted_now, n_overloaded_now=n_over_now,
            n_rejected=nrej_all, n_badmsg=nbad_all,
            n_deadletter=ndl_all, n_mutes=nmut_all,
            qw_p99=qw_p99,
            spawn=spawn_aux,
        )
        return st2, aux

    return local_step


def aux_go(aux: StepAux):
    """The window-continue vote: device work remains and no fact that
    demands host attention (host mailboxes, exit, fatal flags) is up.
    Shared by the in-window while condition and the tick-0 gate of the
    pipelined dispatch (build_multi_step_gated) so the two can never
    disagree about what "host attention" means."""
    go = (aux.device_pending & ~aux.host_pending & ~aux.exit_flag
          & ~aux.spill_overflow & ~aux.spawn_fail
          & ~aux.blob_fail & ~aux.blob_budget_fail)
    if aux.spawn:
        # the next tick's reservations would outrun the free rows: the
        # host collects first (StepAux.spawn)
        go = go & (aux.spawn["room"] >= 0)
    return go


def build_multi_step_gated(program: Program, opts: RuntimeOptions):
    """Fuse up to `limit` scheduler ticks into ONE device dispatch, with
    tick 0 gated ON DEVICE by the PREVIOUS window's aux.

    ≙ the reference amortising scheduler-queue traffic by letting an actor
    drain up to `batch` messages per visit (actor.c:20): here the *host*
    is the expensive queue hop — each jitted call costs a fixed launch
    overhead — so one call advances many ticks under `lax.while_loop`.

    The window ends early the moment the host must act: a host-cohort
    mailbox became non-empty (main-thread actors, scheduler.c:179-190),
    a behaviour exited, a fatal flag rose, or the device quiesced. Host
    reaction latency therefore stays one tick, exactly as unfused.

    The gate (the pipelined run loop, runtime.py): `prev_aux` is the aux
    of the window dispatched just before this one, fed back WITHOUT a
    host round-trip. Tick 0 runs iff `force` (the host KNOWS there is
    work: a sync-point dispatch after host-side writes) or `prev_aux`
    voted clean-busy (aux_go). Otherwise the whole window is an identity
    pass returning `prev_aux` unchanged and ticks_run == 0 — so a window
    speculatively dispatched behind an in-flight one can never advance
    the world past an exit/fatal/host-attention boundary the host has
    not yet observed, and a stale "quiet" vote never runs a tick. That
    keeps the CNF/ACK quiescence semantics (scheduler.c:303-480) exact:
    quiescence is only ever declared from an aux that no later tick has
    invalidated.

    Injections land on the first tick only (the host refills next
    window); a gated-out window consumes none (ticks_run == 0 tells the
    host to re-queue them).
    Returns (state, last_aux, ticks_run).
    """
    step = build_step(program, opts)

    def multi(st: RtState, inject_tgt, inject_words, limit, force,
              prev_aux: StepAux):
        # The run loop redispatches this executable with the SAME
        # inject sentinels / limit every window, and XLA was once
        # observed constant-folding the window's while/cond/reduce for
        # seconds when those operands fold to literals. The barrier
        # pins them as runtime values — the loop body compiles once,
        # folding stops at this line.
        inject_tgt, inject_words, limit, force = lax.optimization_barrier(
            (inject_tgt, inject_words, limit, force))

        def cond(carry):
            _st, aux, i = carry
            first = i == 0
            with phase_scope("vote"):
                return (first & (force | aux_go(aux))) | \
                    (~first & (i < limit) & aux_go(aux))

        def body(carry):
            s, _aux, i = carry
            first = i == 0
            it = jnp.where(first, inject_tgt, jnp.int32(-1))
            iw = jnp.where(first, inject_words, jnp.int32(0))
            s2, aux2 = step(s, it, iw)
            if aux2.spawn:
                aux2 = aux2._replace(spawn={**aux2.spawn, "low": jnp.minimum(
                    _aux.spawn["low"], aux2.spawn["low"])})
            return (s2, aux2, i + 1)

        stf, auxf, k = lax.while_loop(cond, body,
                                      (st, prev_aux, jnp.int32(0)))
        return stf, auxf, k

    return multi


def check_kernels(program: Program, opts: RuntimeOptions) -> None:
    """The one gate on the Pallas switches. `pallas=True` /
    `pallas_fused=True` on a program with a dispatching cohort the
    kernel cannot serve (ops.mailbox_kernel.refusal /
    ops.fused_dispatch.refusal, synchronous construction found by the
    verify pass's probe tracing) raises, naming the cohort and the
    reason: never a quiet XLA path under the kernel's name.
    Runtime.start() calls it before the first trace, build_step for
    callers that build a step or window themselves."""
    if not (opts.pallas or opts.pallas_fused):
        return
    from .. import verify
    from ..ops import fused_dispatch as fd
    from ..ops import mailbox_kernel as mk
    dispatching = [ch for ch in program.device_cohorts if ch.behaviours]

    def honour(what, reason):
        if reason:
            raise ValueError(f"{what} cannot be honoured — {reason}")

    if opts.pallas:
        for ch in dispatching:
            honour("pallas=True", mk.refusal(ch))
    if opts.pallas_fused:
        for ch in dispatching:
            sync_init = any(verify.behaviour_effects(
                b, ch.atype, msg_words=opts.msg_words,
                default_max_sends=opts.max_sends).sync_spawns
                for b in ch.behaviours)
            honour("pallas_fused=True", fd.refusal(ch, opts, sync_init))


def build_multi_step(program: Program, opts: RuntimeOptions):
    """The ungated window: `build_multi_step_gated` with tick 0 forced
    (the pre-pipelining signature — bench.py drives it directly;
    zero_aux as prev keeps the carry well-typed)."""
    gated = build_multi_step_gated(program, opts)

    def multi(st: RtState, inject_tgt, inject_words, limit):
        return gated(st, inject_tgt, inject_words, limit,
                     jnp.bool_(True), zero_aux(program))

    return multi


def zero_aux(program: Optional[Program] = None) -> StepAux:
    """The pre-first-tick aux template (device_pending=True so a window's
    while condition admits tick 0; everything else zero/false; for a
    `program` with device spawns, all the room there is)."""
    i32, b = jnp.int32, jnp.bool_
    most = i32(2**31 - 1)
    return StepAux(
        spawn=({"room": most, "low": most, "spawned": i32(0)}
               if program is not None and program.has_device_spawns
               else {}),
        device_pending=b(True), host_pending=b(False),
        any_muted=b(False),
        exit_flag=b(False), exit_code=i32(0),
        spill_overflow=b(False), spawn_fail=b(False),
        blob_fail=b(False), blob_budget_fail=b(False),
        n_processed=i32(0), n_delivered=i32(0),
        occ_sum=i32(0), occ_max=i32(0),
        n_muted_now=i32(0), n_overloaded_now=i32(0),
        n_rejected=i32(0), n_badmsg=i32(0),
        n_deadletter=i32(0), n_mutes=i32(0), qw_p99=i32(0))


def _jit_over_mesh(fn, program: Program, opts: RuntimeOptions, mesh,
                   n_extra: int, extra_in=None):
    """Jit `fn(state, inject_tgt, inject_words, *extras) → (state, aux,
    *outs)` where len(outs) == n_extra; with a mesh, shard_map over the
    'actors' axis first. State is sharded and donated; injections, extras
    and aux are replicated (aux values are each tick's psum votes,
    identical on every shard). `extra_in` names the extra INPUTS' spec
    kinds — "repl" (scalar) or "aux" (a replicated StepAux pytree, the
    gated window's fed-back prev_aux); defaults to n_extra scalars.

    ≙ ponyint_sched_start picking how many schedulers run
    (scheduler.c:1273-1309) — except "schedulers" are mesh shards and the
    assignment is static.
    """
    if program.shards == 1:
        return jax.jit(fn, donate_argnums=(0,))

    from jax.sharding import PartitionSpec as P
    from .state import state_partition_specs
    assert mesh is not None, "sharded program needs a mesh"
    repl = P()
    state_spec = state_partition_specs(program, opts)
    aux_spec = jax.tree.map(lambda _: repl, zero_aux(program))
    if extra_in is None:
        extra_in = ("repl",) * n_extra
    in_extra = tuple(aux_spec if kind == "aux" else repl
                     for kind in extra_in)
    # check_vma off: the per-shard step uses shard-divergent lax.cond
    # predicates (idle cohorts, pressure paths) that the static
    # replication checker rejects.
    mapped = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(state_spec, repl, repl) + in_extra,
        out_specs=(state_spec, aux_spec) + (repl,) * n_extra,
        check_vma=False)
    return jax.jit(mapped, donate_argnums=(0,))


def jit_multi_step(program: Program, opts: RuntimeOptions, mesh=None):
    """Jit the fused window (extra replicated input: tick limit; extra
    replicated output: ticks run — so the while condition and the host's
    step accounting are shard-uniform)."""
    return _jit_over_mesh(build_multi_step(program, opts), program, opts,
                          mesh, n_extra=1)


def jit_multi_step_gated(program: Program, opts: RuntimeOptions,
                         mesh=None):
    """Jit the PIPELINED window (build_multi_step_gated): extra
    replicated inputs (tick limit, force bit, previous aux — all
    shard-uniform by construction), extra replicated output ticks_run.
    The run loop feeds each window's aux straight into the next
    dispatch, so the gate costs no host round-trip."""
    return _jit_over_mesh(build_multi_step_gated(program, opts), program,
                          opts, mesh, n_extra=1,
                          extra_in=("repl", "repl", "aux"))


def jit_step(program: Program, opts: RuntimeOptions, mesh=None):
    """Jit one tick (see _jit_over_mesh for the mesh wrapping)."""
    return _jit_over_mesh(build_step(program, opts), program, opts, mesh,
                          n_extra=0)


def _state_structure(program, opts):
    """A pytree with the same structure as RtState for building specs."""
    from .state import init_state
    return jax.eval_shape(lambda: init_state(program, opts))
