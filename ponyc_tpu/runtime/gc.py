"""Actor garbage collection: whole-world parallel reachability tracing.

≙ the reference's actor-collection machinery, re-designed for TPU:

- ORCA deferred reference counting (src/libponyrt/gc/gc.c:38-435,
  actormap/objectmap) exists because *distributed tracing is impractical
  on CPUs* — actors would have to pause each other. On a TPU the whole
  actor world is one address space of SoA columns, so the idiomatic
  equivalent is a synchronous parallel trace: mark everything reachable
  from the roots with a vectorised frontier propagation, one masked
  scatter per hop, `lax.while_loop` to fixpoint.
- The cycle detector (gc/cycle.c:345-651 scan_grey/collect + CNF/ACK)
  exists because reference counting can't see cycles. Tracing collects
  cycles for free — a cycle of blocked actors unreachable from any root
  is simply never marked.

Roots (≙ "rc > 0" in ORCA terms):
  - host-pinned actors (Runtime.spawn pins; release() unpins) ≙ the
    external/application reference an actor is born with (actor.c:688);
  - actors with queued or in-flight (spilled) messages ≙ messages hold
    rc while in flight (ORCA's send-increment rule);
  - muted actors (they have rejected traffic parked in a spill);
  - host-cohort rows (host actors are host-managed, never collected);
  - extra host-side roots passed per collection: refs held in host-actor
    state dicts and in the pending inject queue.

Edges: Ref-typed state fields of live actors, and Ref-typed arguments of
every queued/spilled message (the behaviour signature's Ref annotations
are the trace functions ≙ the compiler-generated gentrace.c ones).

Termination: each iteration extends reachability by one hop, so the loop
runs at most graph-diameter times; `gc_max_iters` (0 = unbounded) caps
pathological chains — if the cap is hit before fixpoint, *nothing* is
collected that round (conservative, always safe).

Collection frees the slot (alive=False) — the row becomes claimable by
ctx.spawn / Runtime.spawn. Sends to a collected actor dead-letter, which
Pony's type system makes unrepresentable; here it is a counted drop.

The same pass sweeps the device blob pool (≙ an actor's heap dying with
the actor, mem/heap.c): a pool slot survives iff a surviving actor's
Blob field holds its handle, a queued/spilled/injected message's Blob
argument carries it, or the host owns it (blob_store not yet sent).
Marking is shard-local by design — after migration (engine._route moves
a blob WITH its routed message) every reachable handle is local to its
pool's shard; the rare off-shard handle (host injection without
near=, or a migration drop) is undereferenceable and is collected.
"""

from __future__ import annotations



import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..config import RuntimeOptions
from ..program import Program
from .state import RtState, phase_scope


def build_ref_arg_mask(program: Program, msg_words: int) -> np.ndarray:
    """Static [n_gids, msg_words] bool: which payload words of each
    behaviour message are actor refs (≙ the per-type trace function the
    compiler emits, gentrace.c — here derived from Ref annotations)."""
    from ..ops.pack import is_ref, spec_width
    n = len(program.behaviour_table)
    mask = np.zeros((max(n, 1), msg_words), bool)
    for gid, bdef in enumerate(program.behaviour_table):
        off = 0
        for spec in bdef.arg_specs:
            if is_ref(spec) and off < msg_words:
                mask[gid, off] = True
            off += spec_width(spec)
    return mask


def _ref_fields(cohort):
    from ..ops.pack import is_ref
    return [f for f, spec in cohort.atype.field_specs.items()
            if is_ref(spec)]


def build_blob_arg_mask(program: Program, msg_words: int,
                        mode: str | None = None) -> np.ndarray:
    """Static [n_gids, msg_words] bool: which payload words of each
    behaviour message are device blob handles (the Blob twin of
    build_ref_arg_mask — ≙ gentrace.c tracing message object fields).
    `mode` narrows to one capability ("iso": owned/moving handles,
    "val": shared-immutable); None = both."""
    from ..ops.pack import is_blob, spec_width
    n = len(program.behaviour_table)
    mask = np.zeros((max(n, 1), msg_words), bool)
    for gid, bdef in enumerate(program.behaviour_table):
        off = 0
        for spec in bdef.arg_specs:
            if (is_blob(spec) and off < msg_words
                    and (mode is None or spec.mode == mode)):
                mask[gid, off] = True
            off += spec_width(spec)
    return mask


def _blob_fields(cohort):
    from ..ops.pack import is_blob
    return [f for f, spec in cohort.atype.field_specs.items()
            if is_blob(spec)]


def build_gc(program: Program, opts: RuntimeOptions):
    """Trace the collection pass; returns local_gc(state, extra_roots)
    → (state, (n_collected_total, converged, iters)) in per-shard
    coordinates (wrap like the step: jit for P=1, shard_map for P>1)."""
    assert program.frozen
    p = program.shards
    nl = program.n_local
    ntot = p * nl
    fh = program.first_host_row
    cap = opts.mailbox_cap
    ref_mask_np = build_ref_arg_mask(program, opts.msg_words)
    any_ref_args = bool(ref_mask_np.any())
    n_gids = ref_mask_np.shape[0]
    max_iters = opts.gc_max_iters
    bsl = opts.blob_slots
    blob_mask_np = build_blob_arg_mask(program, opts.msg_words)
    any_blob_args = bool(blob_mask_np.any())
    # Sweep whenever the pool is live and ANY cohort can allocate or
    # carry handles: a program whose handles never escape the allocating
    # behaviour (no Blob fields/args) makes every unfreed blob garbage
    # by construction — exactly what the sweep must reclaim.
    sweep_blobs = bsl > 0 and (any_blob_args
                               or any(_blob_fields(c)
                                      for c in program.cohorts)
                               or any(c.blob_sites
                                      for c in program.cohorts))

    def local_gc(st: RtState, extra_roots, blob_roots):
        with phase_scope("gc_mark"):
            return mark_and_sweep(st, extra_roots, blob_roots)

    def mark_and_sweep(st: RtState, extra_roots, blob_roots):
        if p > 1:
            shard = lax.axis_index("actors").astype(jnp.int32)
        else:
            shard = jnp.int32(0)
        base = shard * nl
        occ = st.tail - st.head
        rows = jnp.arange(nl, dtype=jnp.int32)

        # --- roots ---
        roots = (st.pinned | extra_roots | (occ > 0) | st.muted
                 | (rows >= fh))

        # Initial global marks: local roots + in-flight spill traffic.
        marks0 = jnp.zeros((ntot,), jnp.bool_).at[
            jnp.where(roots, base + rows, ntot)].max(True, mode="drop")
        for tgt_arr, words_arr in (
                (jnp.where(st.dspill_tgt >= 0, base + st.dspill_tgt, -1),
                 st.dspill_words),                 # words planar [w1, S]
                (st.rspill_tgt, st.rspill_words)):
            marks0 = marks0.at[jnp.where(tgt_arr >= 0, tgt_arr, ntot)].max(
                True, mode="drop")
            if any_ref_args:
                gid = words_arr[0]
                g = jnp.clip(gid, 0, n_gids - 1)
                inr = (gid >= 0) & (gid < n_gids) & (tgt_arr >= 0)
                # Payload words only: with tracing on the spill tables
                # carry two trailing (trace_id, parent_span) rows that
                # are never refs.
                for w in range(min(words_arr.shape[0] - 1,
                                   opts.msg_words)):
                    rm = jnp.asarray(ref_mask_np)[g, w] & inr
                    refs = jnp.where(rm, words_arr[1 + w], -1)
                    marks0 = marks0.at[
                        jnp.where(refs >= 0, refs, ntot)].max(
                        True, mode="drop")

        # Pre-extract edges (targets are global ids; sources are local).
        # State-field edges, one [local_cap] target column per Ref field.
        field_edges = []   # (src_slice_start, src_slice_stop, targets)
        for cohort in program.device_cohorts:
            for fname in _ref_fields(cohort):
                col = st.type_state[cohort.atype.__name__][fname]
                field_edges.append((cohort.local_start, cohort.local_stop,
                                    col.astype(jnp.int32)))
        # Mailbox edges: ref args of queued messages. Planar over each
        # cohort's [cap, w1_c, rows] table (per-cohort widths): ring slot
        # ci holds a live message iff (ci - head) mod cap < occupancy;
        # each payload word that the static ref mask marks contributes a
        # [rows_c]-wide plane padded into an [nl] lane (targets are -1
        # outside the cohort's rows).
        # ONE walk serves both masks (ref args feed the actor trace,
        # Blob args feed the blob sweep) — the ring-validity and gid
        # computations are shared per (cohort, slot).
        mb_planes = []                                    # [nl] each
        mbb_planes = []                                   # blob handles
        if any_ref_args or (sweep_blobs and any_blob_args):
            rmask = jnp.asarray(ref_mask_np)
            bmask = jnp.asarray(blob_mask_np)
            for cohort in program.cohorts:
                cbuf = st.buf[cohort.atype.__name__]
                s0, s1 = cohort.local_start, cohort.local_stop
                if cbuf.shape[1] <= 1:
                    continue                   # gid-only mailboxes: no refs
                for ci in range(cap):
                    valid = ((ci - st.head[s0:s1]) % cap) < occ[s0:s1]
                    gid = cbuf[ci, 0]
                    g = jnp.clip(gid, 0, n_gids - 1)
                    inr = valid & (gid >= 0) & (gid < n_gids)
                    for w in range(cbuf.shape[1] - 1):
                        if any_ref_args:
                            rm = rmask[g, w] & inr
                            plane = jnp.full((nl,), -1, jnp.int32).at[
                                s0 + jnp.arange(s1 - s0)].set(
                                jnp.where(rm, cbuf[ci, 1 + w], -1))
                            mb_planes.append(plane)
                        if sweep_blobs and any_blob_args:
                            bmm = bmask[g, w] & inr
                            mbb_planes.append(
                                jnp.where(bmm, cbuf[ci, 1 + w], -1))
        mb_tgt = jnp.stack(mb_planes) if mb_planes else None

        def propagate(live):
            """One hop: mark every target referenced by a live source."""
            marks = jnp.zeros((ntot,), jnp.bool_).at[
                jnp.where(live, base + rows, ntot)].max(True, mode="drop")
            for s0, s1, tgt in field_edges:
                src_ok = live[s0:s1] & st.alive[s0:s1] & (tgt >= 0)
                marks = marks.at[jnp.where(src_ok, tgt, ntot)].max(
                    True, mode="drop")
            if mb_tgt is not None:
                src_ok = live[None, :] & (mb_tgt >= 0)
                marks = marks.at[
                    jnp.where(src_ok, mb_tgt, ntot).reshape(-1)].max(
                    True, mode="drop")
            return marks

        def glob(marks):
            if p > 1:
                marks = lax.psum(marks.astype(jnp.int32), "actors") > 0
            return lax.dynamic_slice(marks, (base,), (nl,))

        live0 = glob(marks0)

        def cond(carry):
            _, changed, it = carry
            going = changed
            if max_iters:
                going = going & (it < max_iters)
            return going

        def body(carry):
            live, _, it = carry
            new_live = live | glob(propagate(live))
            ch = jnp.any(new_live != live)
            if p > 1:
                ch = lax.psum(ch.astype(jnp.int32), "actors") > 0
            return new_live, ch, it + 1

        live, changed, iters = lax.while_loop(
            cond, body, (live0, jnp.bool_(True), jnp.int32(0)))
        converged = ~changed

        # --- collect (only on a converged trace; ≙ cycle.c `collect`) ---
        dead = st.alive & ~live & (rows < fh) & converged
        n_dead = jnp.sum(dead.astype(jnp.int32))

        # --- blob sweep (≙ an actor's heap dying with it, gc.c/heap.c):
        # a pool slot stays allocated iff a surviving actor's Blob FIELD
        # holds it, a queued/spilled message's Blob ARG carries it, or
        # the host declared it a root (rt.blob_store handles not yet
        # sent). Marking is shard-LOCAL on purpose: migration
        # (engine._route) re-homes a payload WITH its routed message,
        # so every resting reachable handle is local to its pool's
        # shard; the rare off-shard handle (host injection without
        # near=, migration drop) is undereferenceable and collects.
        n_swept = jnp.int32(0)
        blob_used2, blob_len2 = st.blob_used, st.blob_len
        nbf2 = st.n_blob_free
        if sweep_blobs:
            bbase = shard * bsl
            alive2 = st.alive & ~dead

            from ..ops import pack as _pk

            def bmark(marks, handles, ok):
                """Mark gen-MATCHING local handles only: a stale handle
                to a recycled slot is dead and must not keep the new
                occupant alive (ops.pack handle encoding)."""
                hl = _pk.blob_slot(handles) - bbase
                good = ok & (handles >= 0) & (hl >= 0) & (hl < bsl)
                hs = jnp.where(good, hl, bsl)
                good = good & (jnp.take(st.blob_gen, hs, mode="fill",
                                        fill_value=-1)
                               == _pk.blob_gen_of(handles))
                return marks.at[jnp.where(good, hl, bsl)].max(
                    True, mode="drop")

            bm = blob_roots
            for cohort in program.device_cohorts:
                s0, s1 = cohort.local_start, cohort.local_stop
                for fname in _blob_fields(cohort):
                    col = st.type_state[cohort.atype.__name__][fname]
                    bm = bmark(bm, col.astype(jnp.int32), alive2[s0:s1])
            if any_blob_args:
                bmask2 = jnp.asarray(blob_mask_np)
                for tgt_arr, words_arr in (
                        (st.dspill_tgt, st.dspill_words),
                        (st.rspill_tgt, st.rspill_words)):
                    gid = words_arr[0]
                    g = jnp.clip(gid, 0, n_gids - 1)
                    inr = (gid >= 0) & (gid < n_gids) & (tgt_arr >= 0)
                    for w in range(min(words_arr.shape[0] - 1,
                                       opts.msg_words)):
                        bm = bmark(bm, words_arr[1 + w],
                                   bmask2[g, w] & inr)
                # Queued-message handles: planes collected by the shared
                # mailbox walk above (-1 where not a valid Blob arg).
                for bplane in mbb_planes:
                    bm = bmark(bm, bplane, bplane >= 0)
            swept = st.blob_used & ~bm
            n_swept = jnp.sum(swept.astype(jnp.int32))
            blob_used2 = st.blob_used & bm
            blob_len2 = jnp.where(swept, 0, st.blob_len)
            nbf2 = st.n_blob_free + n_swept.reshape(1)

        st2 = RtState(
            buf=st.buf,
            head=jnp.where(dead, st.tail, st.head),
            tail=st.tail,
            alive=st.alive & ~dead,
            muted=st.muted & ~dead,
            mute_refs=jnp.where(dead[None, :], -1, st.mute_refs),
            mute_age=jnp.where(dead, 0, st.mute_age),
            mute_ovf=st.mute_ovf & ~dead,
            pinned=st.pinned & ~dead,
            pressured=st.pressured & ~dead,
            dspill_tgt=st.dspill_tgt, dspill_sender=st.dspill_sender,
            dspill_words=st.dspill_words, dspill_count=st.dspill_count,
            rspill_tgt=st.rspill_tgt, rspill_sender=st.rspill_sender,
            rspill_words=st.rspill_words, rspill_count=st.rspill_count,
            spill_overflow=st.spill_overflow,
            exit_flag=st.exit_flag, exit_code=st.exit_code,
            step_no=st.step_no,
            n_processed=st.n_processed, n_delivered=st.n_delivered,
            n_rejected=st.n_rejected, n_badmsg=st.n_badmsg,
            n_deadletter=st.n_deadletter, n_mutes=st.n_mutes,
            n_spawned=st.n_spawned, n_destroyed=st.n_destroyed,
            spawn_fail=st.spawn_fail,
            n_collected=st.n_collected + n_dead.reshape(1),
            last_error=jnp.where(dead, 0, st.last_error),
            last_error_loc=jnp.where(dead, 0, st.last_error_loc),
            n_errors=st.n_errors,
            ev_data=st.ev_data, ev_count=st.ev_count,
            ev_dropped=st.ev_dropped,
            # Profiler lanes pass through untouched: collection frees
            # actors, it dispatches nothing — the window stats the
            # profiler reports about GC itself (passes run, actors
            # collected, blob slots swept) ride this function's return
            # values into Runtime.gc()'s host accounting.
            beh_runs=st.beh_runs, beh_delivered=st.beh_delivered,
            beh_rejected=st.beh_rejected,
            coh_mute_ticks=st.coh_mute_ticks,
            qwait_hist=st.qwait_hist, qwait_enq=st.qwait_enq,
            phase_cost=st.phase_cost,
            # Trace lanes/span ring pass through: collection dispatches
            # nothing, so no spans; dead rows' ring-slot lanes are
            # unreadable (head := tail) and re-stamped on next delivery.
            trace_buf=st.trace_buf, span_data=st.span_data,
            span_count=st.span_count, span_dropped=st.span_dropped,
            span_next=st.span_next,
            # Plan cache passes through: next step's key vector is
            # computed against the new `alive`, so deliveries to
            # collected actors invalidate it by comparison, not here.
            plan_key=st.plan_key, plan_perm=st.plan_perm,
            plan_bounds=st.plan_bounds,
            # Collection can only CLEAR muted/pressured bits (dead rows);
            # stale-high world bits cost one extra gather next tick and
            # the vote then corrects them.
            world_bits=st.world_bits,
            # Blob pool: swept by the mark pass above (data words left in
            # place — a freed slot zeroes on its next alloc).
            blob_data=st.blob_data, blob_used=blob_used2,
            blob_len=blob_len2, blob_gen=st.blob_gen,
            blob_fail=st.blob_fail,
            blob_budget_fail=st.blob_budget_fail,
            n_blob_alloc=st.n_blob_alloc, n_blob_free=nbf2,
            n_blob_remote=st.n_blob_remote,
            n_blob_moved=st.n_blob_moved,
            type_state=st.type_state,
        )
        if p > 1:
            n_dead = lax.psum(n_dead, "actors")
            n_swept = lax.psum(n_swept, "actors")
        return st2, (n_dead, converged, iters, n_swept)

    return local_gc


def jit_gc(program: Program, opts: RuntimeOptions, mesh=None):
    """Jit the collection pass (shard_map over 'actors' when meshed)."""
    gc = build_gc(program, opts)
    if program.shards == 1:
        return jax.jit(gc, donate_argnums=(0,))
    from jax.sharding import PartitionSpec as P
    from .state import state_partition_specs
    sharded = P("actors")
    repl = P()
    state_spec = state_partition_specs(program, opts)
    mapped = jax.shard_map(           # check_vma: see engine._jit_over_mesh
        gc, mesh=mesh,
        in_specs=(state_spec, sharded, sharded),
        out_specs=(state_spec, (repl, repl, repl, repl)),
        check_vma=False)
    return jax.jit(mapped, donate_argnums=(0,))
