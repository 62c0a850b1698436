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

import functools
from collections import namedtuple
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..api import BlobPoolView, Context
from ..config import RuntimeOptions
from ..ops import pack
from ..program import Cohort, Program
from . import lanes, mute, route, spawn
from .delivery import Entries
from .gc import build_blob_arg_mask
from .state import (PhaseCursor, RtState, TickStatic, cohort_scope,
                    counts_pool, counts_prefix, phase_scope, ring_take,
                    rows_of)


# Selects a cohort's drain may unroll: `batch` ring_takes of `cap - 1`
# selects each. Up to here the takes are one straight line the compiler
# fuses (every cohort at RuntimeOptions' default geometry, 8 x 63); past
# it one take is the body of a loop: a coordinator that drains the
# reference's own batch of 100 from a ring of 256 would be 25,500
# selects, which the v5e's compiler had not finished after a quarter of
# an hour (PERF.md, PR 44), for rows so few that the loop costs nothing.
DRAIN_UNROLL = 4096


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
    lists: dict = {}             # which list delivery ran over, where it
    #   may hold two (state.counts_prefix; {} elsewhere: no leaf).
    #   "n_prefix" int32 — *cumulative* shard-ticks delivered over the
    #   list's prefix (the state's route_counts["n_prefix"], mesh-wide).
    pool: dict = {}              # the blob pool's books, where a cohort
    #   can allocate or free in the window (state.counts_pool; {}
    #   elsewhere: no leaf). "alloc", "free" int32 — *cumulative* slots
    #   claimed and released (the state's n_blob_alloc / n_blob_free,
    #   mesh-wide; the host's stores and frees between windows count,
    #   and a payload moved over the mesh is one of each): their
    #   difference is the slots in use at the window's end.


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
    ctx.kept = frozenset(k for k, v in st2.items() if v is st[k])
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
    engine skip dead scatters; for a pool-using cohort also which state
    fields every behaviour handed back untouched, `kept`, and whether
    any allocates or frees, `alloc_free` — what `pinned` is decided
    from, _cohort_dispatch —, a behaviour's allocs and how its
    blob_sets and blob_gets went, `born_full`, and the read windows its
    probe priced, `reads`: api.BlobPoolView.read_plan). pinned: {Blob
    field: (slot, ok, used)} checked before the batch scan; the view
    finds them by the field's tracer."""
    w1 = 1 + msg_words

    def branch(st, payload, ids_vec, resv_k, blob_in=None, take=None,
               pinned=None):
        bv = None
        if blob_in is not None:
            # (pool arrays threaded sequentially through the branches —
            # see api.BlobPoolView for why no cross-branch select is
            # needed; resv row may be zero-sites for receive-only types.)
            bdata, bused, blen, bgen, bbase, bresv, bover = blob_in
            resolved = pack.RefTypes() if pinned else None
            for f, checked in (pinned or {}).items():
                resolved.tag(st[f], checked)
            # The first trace of a behaviour is the cohort's probe: its
            # view records the static reads, and every later trace
            # opens the read windows that record priced.
            plan = effects["reads"].get(bdef.name)
            bv = BlobPoolView(bdata, bused, blen, bgen, bbase,
                              (take if take is not None
                               else jnp.ones((lanes,), jnp.bool_)),
                              bresv if (bresv is not None
                                        and bresv.shape[0]) else None,
                              budget_over=bover, resolved=resolved,
                              reads=plan or ())
        ctx, st2, tgts, words = eval_behaviour(
            bdef, st, payload, ids_vec, msg_words=msg_words,
            field_specs=field_specs, field_dtypes=field_dtypes,
            lanes=lanes, max_sends=max_sends, spawn_resv=resv_k,
            spawn_meta=spawn_meta, blob=bv)
        effects["destroy"] = effects["destroy"] or ctx.destroy_called
        effects["error"] = effects["error"] or ctx.error_called
        effects["sync_init"] = (effects["sync_init"]
                                or bool(ctx.sync_inits))
        if bv is not None:
            # The payloads this behaviour allocated and filled reach the
            # pool here at the latest, one column scatter each.
            bv.flush()
            reads = bv.read_plan()
            if plan is None:
                effects["reads"][bdef.name] = reads
            elif reads != plan:
                raise RuntimeError(
                    f"behaviour {bdef} reads its payloads' words as "
                    f"{reads} where its probe saw {plan}: "
                    "the probe and the trace disagree (engine wiring)")
            effects["born_full"][bdef.name] = {
                "allocs": bv.claims, "sets_folded": bv.sets_folded,
                "sets_alone": bv.sets_alone, **bv.read_facts()}
            alloc_free = bool(bv.claims or bv.frees)
            if pinned and (alloc_free or not set(pinned) <= ctx.kept):
                raise RuntimeError(
                    f"behaviour {bdef} allocates, frees or overwrites a "
                    f"handle its cohort checks once ({sorted(pinned)}): "
                    "the probe and the trace disagree (engine wiring)")
            effects["alloc_free"] = effects["alloc_free"] or alloc_free
            effects["kept"] &= ctx.kept
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



def _cohort_dispatch(cohort: Cohort, opts: RuntimeOptions, noyield: bool,
                     program: Program):
    """Build the planar per-cohort drain loop.

    ≙ ponyint_actor_run (actor.c:383-549): pop ≤batch app messages,
    dispatch each, honour yield (fork: actor.c:675-679), count
    consumption — for every actor of the cohort at once, as [rows]-wide
    vector ops (actors on the 128 TPU lanes, batch slots iterated by a
    lax.scan whose carries are lane-shaped, all but a pool-using
    cohort's: the pool's [W*B] words ride the carry too).
    """
    msg_words = opts.msg_words          # OUTBOX width (program-wide max)
    ms = cohort.max_sends
    batch = cohort.batch
    cap = cohort.mailbox_cap
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
    effects = {"destroy": False, "error": False, "sync_init": False,
               "alloc_free": False, "pinned": None, "born_full": {},
               "reads": {},
               "kept": frozenset(cohort.atype.field_specs)}
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
    sds = jax.ShapeDtypeStruct

    def probe(*pool):
        """One abstract trace of every branch: `effects` is known
        before anything is built from it."""
        for br in branches:
            jax.eval_shape(
                br,
                {f: sds((rows,), field_dtypes[f])
                 for f in cohort.atype.field_specs},
                sds((cohort.msg_words, rows), jnp.int32),
                sds((rows,), jnp.int32),
                {t: sds((n, rows), jnp.int32) for t, n in spawn_sites},
                *pool)

    def pinned_fields():
        """Which handles are checked once a dispatch, not once a message
        (api.BlobPoolView): a Blob field every behaviour hands back as
        the tracer it was given, in a cohort that neither allocates nor
        frees — the field, `gen` and `used` are then the scan's
        invariants. Decided from the behaviours' own trace over the
        pool's shapes (a read window is priced by the slots a lane:
        api.window_rows), the first time the cohort is traced (a
        behaviour that cannot be traced fails where it always did: at
        the first run)."""
        if effects["pinned"] is None:
            effects["pinned"] = ()
            if use_blob and nb:
                slots = sds((opts.blob_slots,), jnp.int32)
                probe((sds((opts.blob_words * opts.blob_slots,), jnp.int32),
                       sds((opts.blob_slots,), jnp.bool_), slots, slots,
                       sds((), jnp.int32),
                       sds((cohort.blob_sites, rows), jnp.int32),
                       sds((rows,), jnp.bool_)), sds((rows,), jnp.bool_))
                if not effects["alloc_free"]:
                    effects["pinned"] = tuple(
                        f for f, spec in cohort.atype.field_specs.items()
                        if pack.is_blob(spec) and f in effects["kept"])
        return effects["pinned"]

    if opts.pallas_fused and nb >= 1:
        from ..ops import fused_dispatch as fd
        from ..ops import mailbox_kernel as mk
        # Probe-trace every branch so `effects` is discovered BEFORE
        # the kernel is built (it hosts destroy/error/spawn claims as
        # lane planes but cannot host sync-construction packaging).
        if not use_blob:
            probe()
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

        def scan_body(pinned, carry, x):
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
                with phase_scope("dispatch/heap/reserve",
                                 when=cohort.blob_sites > 0):
                    rblob = jnp.full(rt_b.shape[1:], -1, jnp.int32)
                    for d in range(rt_b.shape[0]):
                        rblob = jnp.where((bused_c == d)[None, :], rt_b[d],
                                          rblob)
                    # Lanes whose window was withheld for BUDGET
                    # (allocating dispatch count past BLOB_DISPATCHES) —
                    # an alloc failure there blames the budget knob, not
                    # the pool size.
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
                    st, msg[1:], ids, resv_k, blob_in, take, pinned)
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
                    def take(k):
                        return ring_take(buf_rows, (head_rows + k) % cap)

                    if batch * (cap - 1) > DRAIN_UNROLL:
                        msgs = lax.map(take, jnp.arange(batch,
                                                        dtype=jnp.int32))
                    else:
                        msgs = jnp.stack([take(k) for k in range(batch)])
                    # [batch, w1, rows]
                    valids = (jnp.arange(batch, dtype=jnp.int32)[:, None]
                              < n_run[None, :])         # [batch, rows]
            z = lambda d: jnp.zeros((rows,), d)         # noqa: E731
            if use_blob:
                blb0 = (blob["data"], blob["used"], blob["len"],
                        blob["gen"], jnp.bool_(False), jnp.bool_(False),
                        jnp.int32(0), jnp.int32(0), jnp.int32(0))
            else:
                blb0 = None
            # A pinned handle (pinned_fields, above) is checked here,
            # once: its slot, its generation, the slot's used flag — the
            # two gathers every batch slot made. Of what the scan
            # carries, the pool's [W*B] words are NOT lane-shaped; what
            # it closes over here is: three lane vectors a field.
            pinned, fields = {}, pinned_fields()
            if fields:
                with phase_scope("dispatch/heap"):
                    view = BlobPoolView(*blb0[:4], blob["base"],
                                        z(jnp.bool_), None)
                    for f in fields:
                        h = type_state_rows[f]
                        hl, ok = view.local(h)
                        pinned[f] = (hl, ok, view.live(h, hl))
            carry0 = (type_state_rows, z(jnp.bool_), z(jnp.bool_),
                      z(jnp.int32), z(jnp.bool_), z(jnp.bool_),
                      z(jnp.bool_), z(jnp.int32), z(jnp.int32),
                      z(jnp.int32), z(jnp.int32), z(jnp.int32), blb0,
                      z(jnp.int32))
            ((stf, _, ef, ec, sfail, dstr, errf, errc, errl, _used, nproc,
              nbad, blbf, _bused),
             (stgt, swrd, consumed, claims, inits)) = lax.scan(
                functools.partial(scan_body, pinned), carry0,
                (msgs, valids))
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

    def born_full():
        """How often a payload is born full and read whole
        (api.BlobPoolView): the cohort's blob_allocs, the blob_sets an
        open column took and the ones that scattered a word a lane, the
        read windows its behaviours open, the blob_gets those stand for
        and the ones that gather a word a lane, summed over its
        behaviours — read off the same abstract trace as
        `pinned_fields`."""
        pinned_fields()
        return {k: sum(b[k] for b in effects["born_full"].values())
                for k in POOL_FACTS}

    run_cohort.pinned_fields = pinned_fields
    run_cohort.born_full = born_full
    return run_cohort


def _pool_facts(program: Program, opts: RuntimeOptions, fact: str,
                none) -> Dict[str, Any]:
    """{actor type: what its dispatch's own trace says of the pool}
    (`run_cohort.<fact>()`), a device cohort a row; `none` for a cohort
    without the pool."""
    return {ch.atype.__name__: (getattr(_cohort_dispatch(
                ch, opts, opts.noyield, program), fact)()
            if opts.blob_slots > 0 and ch.uses_blobs else none)
            for ch in program.device_cohorts}


def pinned_handles(program: Program, opts: RuntimeOptions
                   ) -> Dict[str, List[str]]:
    """{actor type: the Blob state fields its dispatch checks once}, a
    device cohort a row ([] for a cohort that checks where it uses):
    the analysis dump's `pinned_handles`."""
    return {t: list(f) for t, f in _pool_facts(
        program, opts, "pinned_fields", ()).items()}


# What `born_full` counts of a cohort's blob ops, in the dump's order.
POOL_FACTS = ("allocs", "sets_folded", "sets_alone", "windows",
              "gets_windowed", "gets_alone")


def born_full(program: Program, opts: RuntimeOptions
              ) -> Dict[str, Dict[str, int]]:
    """{actor type: {fact: count}} over POOL_FACTS, a device cohort a
    row: its behaviours' blob_alloc sites, the blob_sets that landed in
    a fresh payload's column and the ones that wrote the pool a word a
    lane; the read windows they open, the blob_gets a window answered
    and the ones that gathered a word a lane (api.BlobPoolView; all 0
    for a cohort without the pool) — the analysis dump's `born_full`."""
    return {t: dict(n) for t, n in _pool_facts(
        program, opts, "born_full", dict.fromkeys(POOL_FACTS, 0)).items()}


def tick_static(program: Program, opts: RuntimeOptions) -> TickStatic:
    """Everything a tick knows before it is traced, worked out once."""
    dev_cohorts = program.device_cohorts
    blob_route = None
    if opts.blob_slots > 0 and program.shards > 1:
        mask = build_blob_arg_mask(program, opts.msg_words)
        if mask.any():
            blob_route = (mask, build_blob_arg_mask(
                program, opts.msg_words, mode="iso"))
    pri_sorted = sorted({ch.priority for ch in dev_cohorts}, reverse=True)
    return TickStatic(
        program=program, opts=opts, p=program.shards, nl=program.n_local,
        fh=program.first_host_row,
        s_cap=opts.spill_cap, lists=route.list_sizes(program, opts),
        pri_rank={pv: i for i, pv in enumerate(pri_sorted)},
        n_levels=2 + max(1, len(pri_sorted)),
        cohort_layout=tuple(
            (ch.atype.__name__, ch.local_start, ch.local_stop,
             1 + ch.msg_words) for ch in program.cohorts),
        blob_route=blob_route,
        dispatchers=tuple(
            (_cohort_dispatch(ch, opts, opts.noyield, program), ch)
            for ch in dev_cohorts))


# What step 2 leaves. `head`: [nl] heads after the drains; `out_entries`:
# one outbox a device cohort; `claim_lists` / `init_lists`: target type ->
# the refs each spawner cohort claimed and their sync-init values;
# `destroy_rows` / `error_rows`: (s0, [rows] bool) and (s0, ([rows] bool,
# codes)) a cohort; `drain_facts`: (cohort, head before, head after),
# for the profiler lanes (lanes.profile_lanes) when analysis >= 1.
Dispatched = namedtuple(
    "Dispatched", "type_state head out_entries claim_lists init_lists "
    "destroy_rows error_rows exit_f exit_c spawn_fail nproc nbad drain_facts "
    "pool")
# The rows after the tick's destroys and errors (step 4b).
Lifecycle = namedtuple(
    "Lifecycle", "alive head muted mute_refs mute_ovf pinned pressured "
    "last_error last_error_loc n_errors n_destroyed")


def dispatch(k: TickStatic, st: RtState, w, rs) -> Dispatched:
    """--- 2. drain + dispatch per cohort (≙ actor run loop)."""
    nl, fh, program, pool = k.nl, k.fh, k.program, rs.pool
    new_type_state: Dict[str, Dict[str, Any]] = dict(st.type_state)
    head_segments: List[jnp.ndarray] = []
    out_entries: List[Entries] = []
    claim_lists: Dict[str, List[jnp.ndarray]] = {
        t: [] for t in program.spawn_target_names}
    init_lists: Dict[str, List[Any]] = {
        t: [] for t in program.spawn_target_names}
    destroy_rows: List[Tuple[int, jnp.ndarray]] = []
    error_rows: List[Tuple[int, Any]] = []
    exit_f = st.exit_flag[0]
    exit_c = st.exit_code[0]
    spawn_fail = st.spawn_fail[0]
    nproc_total = jnp.int32(0)
    nbad_total = jnp.int32(0)
    drain_facts = []
    for run_cohort, ch in k.dispatchers:
        s0, s1 = ch.local_start, ch.local_stop
        ids = w.base + s0 + jnp.arange(ch.local_capacity, dtype=jnp.int32)
        with phase_scope("spawn"):
            resv = spawn.cohort_resv(ch, rs, w)
            if k.opts.blob_slots > 0 and ch.uses_blobs:
                blobd = {**dict(zip(("data", "used", "len", "gen"), pool.cur)),
                         "base": pool.base,
                         "resv": spawn.cohort_blob_resv(ch, rs)}
            else:
                blobd = None
        with phase_scope(cohort_scope(ch.atype.__name__)):
            (stf, out, new_head_rows, ef, ec, nproc, nbad, claims, inits,
             sfail, dstr, errs, blob_out) = run_cohort(
                st.type_state[ch.atype.__name__],
                st.buf[ch.atype.__name__], st.head[s0:s1], w.occ0[s0:s1],
                rs.runnable[s0:s1], ids, resv, blob=blobd)
        if blob_out is not None:
            pool = pool._replace(
                cur=blob_out[:4], fail=pool.fail | blob_out[4],
                budget=pool.budget | blob_out[5],
                n_alloc=pool.n_alloc + blob_out[6],
                n_free=pool.n_free + blob_out[7],
                n_remote=pool.n_remote + blob_out[8])
        new_type_state[ch.atype.__name__] = stf
        head_segments.append(new_head_rows)
        if k.opts.analysis >= 1:
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
    return Dispatched(new_type_state, new_head, out_entries, claim_lists,
                      init_lists, destroy_rows, error_rows, exit_f, exit_c,
                      spawn_fail, nproc_total, nbad_total, drain_facts, pool)


def lifecycle(k: TickStatic, st: RtState, d, new_tail, cl, um) -> Lifecycle:
    """--- 4b. apply destroys (≙ ponyint_actor_setpendingdestroy +
    ponyint_actor_destroy, actor.c:570-664): the slot dies at end of
    step; its remaining queue is discarded (head := tail), flags
    clear, and the row becomes reclaimable by a later spawn. `cl`: the
    rows after the claims, `new_tail` after delivery."""
    nl, alive, new_head = k.nl, cl.alive, cl.head
    muted, mute_refs, mute_ovf = um
    pinned = st.pinned
    pressured = st.pressured
    # Int-coded error residue (≙ pony_error_int/code, fork): latest
    # nonzero code per actor + a counter; zero-cost for cohorts whose
    # behaviours never call ctx.error_int (gated at trace).
    last_error = st.last_error
    last_error_loc = st.last_error_loc
    n_errors = jnp.int32(0)
    for s0, errs in d.error_rows:
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
    for s0, dstr in d.destroy_rows:
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
    return Lifecycle(alive, new_head, muted, mute_refs, mute_ovf, pinned,
                     pressured, last_error, last_error_loc, n_errors,
                     n_destroyed)


def vote(k: TickStatic, st: RtState, w, d, n_spawned, r, life, m, qw_hist2,
         occ_after):
    """--- 6. the vote: the tick's facts reduced to the aux the window's
    continue test (aux_go) and the host read. Returns (aux, this shard's
    cumulative (rejected, badmsg, deadletter, mutes), its sticky spill
    overflow, the next tick's world bits)."""
    p, nl, fh = k.p, k.nl, k.fh
    res, pool, rsp_count = r.res, r.pool, r.rspill_count
    exit_f, exit_c, spawn_fail = d.exit_f, d.exit_c, d.spawn_fail
    nrej_new = st.n_rejected[0] + res.n_rejected
    nbad_new = st.n_badmsg[0] + d.nbad
    ndl_new = st.n_deadletter[0] + res.n_deadletter
    nmut_new = st.n_mutes[0] + jnp.sum(m.became.astype(jnp.int32))
    counts = (nrej_new, nbad_new, ndl_new, nmut_new)
    # which list delivery ran over, where the state counts it
    lists = {name: st.route_counts[name][0] + r.counts[name]
             for name in ("n_prefix",) if name in r.counts}
    # the pool's books, where the window can move them
    books = ({"alloc": st.n_blob_alloc[0] + pool.n_alloc,
              "free": st.n_blob_free[0] + pool.n_free}
             if counts_pool(k.program) else {})
    (occ_sum, occ_max, n_muted_now, n_over_now, nrej_all, nbad_all,
     ndl_all, nmut_all, qw_p99) = lanes.vote_lanes(
        k, occ_after, m.muted, counts, qw_hist2)
    local_pending = (jnp.any(occ_after[:fh] > 0)
                     | (res.spill_count > 0) | (rsp_count > 0))
    any_muted_local = jnp.any(m.muted)
    host_pending = (jnp.any(occ_after[fh:] > 0) if fh < nl
                    else jnp.bool_(False))
    # Sticky: once any step overflowed, every later aux reports it, so
    # the host catches it whatever its fetch cadence (quiesce_interval).
    overflow = st.spill_overflow[0] | res.spill_overflow | r.rspill_over
    # End-of-tick facts feeding the next tick's gather gates (exact,
    # not conservative: `pressured`/`muted2` are post-destroy finals,
    # `rsp_count` is the post-route spill count).
    any_pressured_local = jnp.any(life.pressured)
    any_rspill_local = rsp_count > 0
    facts = (spawn_fail, local_pending, any_muted_local, host_pending,
             exit_f, overflow, any_pressured_local, any_rspill_local)
    if p > 1:
        # A mesh's ninth fact, world bit 3: some row ends the tick
        # overloaded — over its overload line, or with a message parked
        # for it in a receiver spill — which is what `mute.world` will
        # put in the next tick's hot word: the gate of its all-gather
        # and of routing's lookup in it (route._route_spill).
        facts += (jnp.any(occ_after > rows_of(k.program, "overload_occ"))
                  | (res.spill_count > 0),)
        nf = len(facts)
        # ONE packed psum + ONE packed pmax replace the former ~17
        # separate collectives (≙ the CNF/ACK token protocol being a
        # single token, not one message per fact, scheduler.c:303-480).
        # Booleans ride as 0/1 counts ("any" = sum > 0); cumulative
        # counters wrap mod 2^32 exactly as the per-shard counters do.
        i32c = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
        summed = lax.psum(jnp.stack([
            *(i32c(f) for f in facts),
            st.n_processed[0] + d.nproc,
            st.n_delivered[0] + res.n_delivered,
            occ_sum, n_muted_now, n_over_now,
            nrej_all, nbad_all, ndl_all, nmut_all,
            i32c(pool.fail), i32c(pool.budget), *lists.values(),
            *books.values()]), "actors")
        lists = dict(zip(lists, summed[nf + 11:]))
        if books:
            books = dict(zip(books, summed[nf + 11 + len(lists):]))
        *facts, any_overloaded_all = (summed[i] > 0 for i in range(nf))
        nproc_all, ndel_all = summed[nf], summed[nf + 1]
        blob_fail_any, blob_budget_any = (summed[nf + 9] > 0,
                                          summed[nf + 10] > 0)
        if k.opts.analysis >= 1:
            occ_sum, n_muted_now, n_over_now = summed[nf + 2:nf + 5]
            nrej_all, nbad_all, ndl_all, nmut_all = summed[nf + 5:nf + 9]
        maxed = lax.pmax(jnp.stack([
            jnp.where(exit_f, exit_c, jnp.int32(-2**31)), occ_max,
            qw_p99]), "actors")
        exit_code_all = jnp.where(facts[4], maxed[0], exit_c)
        if k.opts.analysis >= 1:
            occ_max = maxed[1]
            qw_p99 = maxed[2]
    else:
        exit_code_all = exit_c
        nproc_all = st.n_processed[0] + d.nproc
        ndel_all = st.n_delivered[0] + res.n_delivered
        blob_fail_any, blob_budget_any = pool.fail, pool.budget
    (spawn_fail_any, device_pending, any_muted_all, host_pending, exit_any,
     overflow_any, any_pressured_all, any_rspill_all) = facts
    spawn_aux = spawn.row_pressure(k, st, w, r, any_rspill_all, life.alive,
                                   life.head, occ_after, n_spawned)
    wb_new =(any_pressured_all.astype(jnp.int32)
              | (any_muted_all.astype(jnp.int32) << 1)
              | (any_rspill_all.astype(jnp.int32) << 2))
    if p > 1:
        wb_new = wb_new | (any_overloaded_all.astype(jnp.int32) << 3)
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
        lists=lists,
        pool=books,
    )
    return aux, counts, overflow, wb_new


def _vec(x, dtype=None):   # per-shard "scalar" → [1]
    return jnp.asarray(x, dtype).reshape(1)


def tick(k: TickStatic, st: RtState, inject_tgt, inject_words,
         phase: PhaseCursor) -> Tuple[RtState, StepAux]:
    """One scheduler tick: its phases in order, each given the static
    record, the state and the earlier phases' results. `phase(name)`
    opens the named scope `pony/<name>` for what is traced from there to
    the next call (state.PhaseCursor)."""
    opts, tracing = k.opts, k.opts.tracing
    phase("unmute")
    w = mute.world(k, st)
    um = mute.unmute_pass(k, st, w)                         # 1
    phase("spawn")
    rs = spawn.reserve(k, st, w, um.muted)                  # 1b, 2a'
    phase("dispatch")
    d = dispatch(k, st, w, rs)                              # 2
    phase("spawn")
    cl = spawn.claim(k, st, w, d)                           # 2b
    # --- 2c. causal-trace spans + context propagation (tracing on
    # only; the Python-level gate keeps the jaxpr bit-identical to
    # a tracer-free build otherwise — tests/test_tracing.py traps
    # trace_span_lanes to prove it). Every cohort's outbox gains
    # two trailing word rows carrying (trace_id, span_id) of the
    # dispatch that emitted each entry; spills, routing and
    # delivery move them with the payload from here on.
    out_entries = d.out_entries
    if tracing:
        phase("analysis")
        (span_data2, span_count2, span_dropped2, span_next2,
         tr_rows) = lanes.trace_span_lanes(k.program, opts, st,
                                           d.drain_facts, w.base, w.shard)
        out_entries = [
            o._replace(words=jnp.concatenate([o.words, t], axis=0))
            for o, t in zip(out_entries, tr_rows)]
    r = route.deliver_routed(k, st, w, inject_tgt, inject_words,
                             out_entries, cl, d.pool, phase)    # 3, 4
    res, pool = r.res, r.pool
    phase("gc_mark")
    life = lifecycle(k, st, d, res.tail, cl, um)            # 4b
    phase("mute")
    m = mute.bookkeeping(st, life, res, r)                  # 5
    occ_after = res.tail - life.head
    ring = (st.ev_data, st.ev_count[0], st.ev_dropped[0])
    if opts.analysis >= 1:
        phase("analysis")
    if opts.analysis >= 3:
        ring = lanes.event_ring(k, st, w, ring, d.error_rows, life,
                                m.became, occ_after)        # 5b
    # --- 5c. per-behaviour profiler lanes (analysis level >= 1 only;
    # the gate is PYTHON-level, so level 0 traces none of this —
    # tests trap profile_lanes to assert exactly that).
    prof = {f: getattr(st, f) for f in lanes.PROFILE_FIELDS}
    if opts.analysis >= 1:
        prof = dict(zip(lanes.PROFILE_FIELDS, (
            *lanes.profile_lanes(k.program, opts, st, cl.tail0, res,
                                 d.drain_facts, m.muted),
            lanes.phase_cost_lanes(
                st, r.listed_tgt, d.drain_facts, d.nproc, cl.n_spawned,
                life.n_destroyed, res.rebuild_slots))))
    phase("vote")
    aux, (nrej_new, nbad_new, ndl_new, nmut_new), overflow, wb_new = vote(
        k, st, w, d, cl.n_spawned, r, life, m, prof["qwait_hist"],
        occ_after)                                          # 6
    st2 = RtState(
        buf=res.buf, head=life.head, tail=res.tail,
        alive=life.alive, muted=m.muted, mute_refs=m.refs,
        mute_age=m.age,
        mute_ovf=m.ovf, pinned=life.pinned, pressured=life.pressured,
        dspill_tgt=res.spill.tgt, dspill_sender=res.spill.sender,
        dspill_words=res.spill.words,
        dspill_count=_vec(res.spill_count),
        rspill_tgt=r.rspill.tgt, rspill_sender=r.rspill.sender,
        rspill_words=r.rspill.words,
        rspill_count=_vec(r.rspill_count),
        route_counts={name: _vec(st.route_counts[name][0] + n)
                      for name, n in r.counts.items()},
        spill_overflow=_vec(overflow, jnp.bool_),
        exit_flag=_vec(d.exit_f, jnp.bool_), exit_code=_vec(d.exit_c),
        step_no=_vec(st.step_no[0] + 1),
        n_processed=_vec(st.n_processed[0] + d.nproc),
        n_delivered=_vec(st.n_delivered[0] + res.n_delivered),
        n_rejected=_vec(nrej_new),
        n_badmsg=_vec(nbad_new),
        n_deadletter=_vec(ndl_new),
        n_mutes=_vec(nmut_new),
        n_spawned=_vec(st.n_spawned[0] + cl.n_spawned),
        n_destroyed=_vec(st.n_destroyed[0] + life.n_destroyed),
        spawn_fail=_vec(d.spawn_fail, jnp.bool_),
        n_collected=st.n_collected,
        last_error=life.last_error, last_error_loc=life.last_error_loc,
        n_errors=_vec(st.n_errors[0] + life.n_errors),
        ev_data=ring[0], ev_count=_vec(ring[1]),
        ev_dropped=_vec(ring[2]),
        **prof,
        trace_buf=res.trace_buf,
        span_data=span_data2 if tracing else st.span_data,
        span_count=(_vec(span_count2) if tracing else st.span_count),
        span_dropped=(_vec(span_dropped2) if tracing
                      else st.span_dropped),
        span_next=(_vec(span_next2) if tracing else st.span_next),
        plan_key=res.plan_key, plan_perm=res.plan_perm,
        plan_bounds=res.plan_bounds,
        world_bits=_vec(wb_new),
        blob_data=pool.cur[0], blob_used=pool.cur[1],
        blob_len=pool.cur[2], blob_gen=pool.cur[3],
        blob_fail=_vec(pool.fail, jnp.bool_),
        blob_budget_fail=_vec(pool.budget, jnp.bool_),
        n_blob_alloc=_vec(st.n_blob_alloc[0] + pool.n_alloc),
        n_blob_free=_vec(st.n_blob_free[0] + pool.n_free),
        n_blob_remote=_vec(st.n_blob_remote[0] + pool.n_remote),
        n_blob_moved=_vec(st.n_blob_moved[0] + r.nb_moved),
        type_state=cl.type_state,
    )
    return st2, aux


def build_step(program: Program, opts: RuntimeOptions):
    """Trace one whole-world scheduler tick; returns a function
    local_step(state, inject_tgt, inject_words) → (state, StepAux) in
    *per-shard* coordinates. Wrap with jit (P=1) or shard_map (P>1) via
    jit_step()."""
    assert program.frozen
    check_kernels(program, opts)
    k = tick_static(program, opts)

    def local_step(st: RtState, inject_tgt, inject_words
                   ) -> Tuple[RtState, StepAux]:
        with PhaseCursor() as phase:
            return tick(k, st, inject_tgt, inject_words, phase)

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


def zero_aux(program: Optional[Program] = None) -> StepAux:
    """The pre-first-tick aux template (device_pending=True so a window's
    while condition admits tick 0; everything else zero/false; for a
    `program`, the leaves its window's aux has besides: with device
    spawns all the room there is, `lists` where its state counts the
    prefix's ticks)."""
    i32, b = jnp.int32, jnp.bool_
    most = i32(2**31 - 1)
    return StepAux(
        spawn=({"room": most, "low": most, "spawned": i32(0)}
               if program is not None and program.has_device_spawns
               else {}),
        lists=({"n_prefix": i32(0)}
               if program is not None and counts_prefix(program) else {}),
        pool=({"alloc": i32(0), "free": i32(0)}
              if program is not None and counts_pool(program) else {}),
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
