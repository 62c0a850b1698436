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

Formulation (one shard and a mesh alike): no scatter in the trace. A
TPU runs a scatter of single words one update after another (~90 ns an
update: a hop over a million rows was 0.1 s a Ref field, and the parent's
mailbox planes — every ring slot of every row, again on every hop — 4
to 12 s); a sort is its cheapest data-dependent move and a gather is
paid per index. So "which ids occur in this list" is a sort and a merge
(`marks_of`: ops.segment.segment_bounds counts, for every id, the
entries below it); the Ref-field edges are sorted by target ONCE a pass
(`sorted_edges`), and a hop is then: gather `live` by the sorted
sources, one prefix sum, gather the sums at every target's bounds —
target t is referenced by a live source iff the sum moves across its
segment. A row that holds a message is a root, so a mailbox edge's
source is live before the first hop: the Ref arguments of queued
messages are marked once, into the initial marks, over the OCCUPIED ring
slots only — rank k of every mailbox at a time, as deep as the fullest
mailbox, not `mailbox_cap` planes. A list a sixteenth of the id space or
shorter (the spills) is still scattered: the merge would sort the whole
id space for it.

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
Marking is shard-local by design — after migration (route._route moves
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
from ..ops.segment import marks_of, segment_bounds
from ..program import Program
from .state import PhaseCursor, RtState, phase_scope, ring_take

def sorted_edges(src, src_ok, tgt, n: int):
    """The edges (row src[i] -> tgt[i]) with `src_ok`, sorted by target:
    (`src_sorted`, the source row of each sorted edge; `below` [n + 1],
    how many sorted edges point below each id). Edges that are not ok or
    point outside [0, n) sort past every id and are never counted."""
    key = jnp.where(src_ok & (tgt >= 0) & (tgt < n), tgt, n)
    key_s, src_sorted = lax.sort((key.astype(jnp.int32), src), num_keys=1)
    return src_sorted, segment_bounds(key_s, n)


def hop_marks(live, src_sorted, below):
    """[n] bool: ids some edge of `sorted_edges` reaches from a live
    source (`src_sorted`: the source row of each sorted edge) — two
    gathers and a prefix sum."""
    hit = jnp.take(live, src_sorted).astype(jnp.int32)
    upto = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(hit)])
    at = jnp.take(upto, below)
    return at[1:] > at[:-1]


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


def _carries(mask: np.ndarray, w: int, gid, among=None):
    """Lanes whose message (behaviour id `gid`) carries in payload word
    `w` what the static `mask` [n_gids, words] marks: a compare a marked
    behaviour (`among`: only these ids can occur, a cohort's own), never
    an indexed read of the mask by a million ids. None where no
    behaviour can: the word is skipped at trace time."""
    hits = [k for k in (range(mask.shape[0]) if among is None else among)
            if w < mask.shape[1] and mask[k, w]]
    out = None
    for k in hits:
        out = (gid == k) if out is None else out | (gid == k)
    return out


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
    """Trace the collection pass; returns local_gc(state, extra_roots,
    blob_roots) → (state, (n_collected_total, converged, iters,
    n_blobs_swept, free_rows_before, n_spawned_so_far)) in per-shard
    coordinates (wrap like the step: jit for P=1, shard_map for P>1)."""
    assert program.frozen
    p = program.shards
    nl = program.n_local
    ntot = p * nl
    fh = program.first_host_row
    ref_mask_np = build_ref_arg_mask(program, opts.msg_words)
    any_ref_args = bool(ref_mask_np.any())
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
        with phase_scope("gc_mark"), PhaseCursor() as phase:
            return mark_and_sweep(st, extra_roots, blob_roots, phase)

    def mark_and_sweep(st: RtState, extra_roots, blob_roots,
                       phase: PhaseCursor):
        # `phase(name)` opens the scope `pony/<name>` for what is traced
        # from there to the next call (state.PhaseCursor).
        if p > 1:
            shard = lax.axis_index("actors").astype(jnp.int32)
        else:
            shard = jnp.int32(0)
        base = shard * nl
        occ = st.tail - st.head
        rows = jnp.arange(nl, dtype=jnp.int32)

        from ..ops import pack as _pk

        def bmark(marks, handles, ok):
            """Mark gen-MATCHING local handles only: a stale handle
            to a recycled slot is dead and must not keep the new
            occupant alive (ops.pack handle encoding)."""
            hl = _pk.blob_slot(handles) - shard * bsl
            good = ok & (handles >= 0) & (hl >= 0) & (hl < bsl)
            hs = jnp.where(good, hl, bsl)
            good = good & (jnp.take(st.blob_gen, hs, mode="fill",
                                    fill_value=-1)
                           == _pk.blob_gen_of(handles))
            return marks.at[jnp.where(good, hl, bsl)].max(
                True, mode="drop")

        # --- roots, and everything a root's own mail names ---
        phase("gc_mark/roots")
        roots = (st.pinned | extra_roots | (occ > 0) | st.muted
                 | (rows >= fh))
        # Initial global marks: the local roots (on one shard the
        # marks ARE the roots) + in-flight spill traffic.
        marks0 = roots if p == 1 else lax.dynamic_update_slice(
            jnp.zeros((ntot,), jnp.bool_), roots, (base,))
        for tgt_arr, words_arr in (
                (jnp.where(st.dspill_tgt >= 0, base + st.dspill_tgt,
                           -1), st.dspill_words),  # words planar [w1, S]
                (st.rspill_tgt, st.rspill_words)):
            named = [tgt_arr]
            # Payload words only: with tracing on the spill tables carry
            # two trailing (trace_id, parent_span) rows that are never
            # refs.
            for w in range(min(words_arr.shape[0] - 1, opts.msg_words)):
                rm = _carries(ref_mask_np, w, words_arr[0])
                if rm is not None:
                    named.append(jnp.where(rm & (tgt_arr >= 0),
                                           words_arr[1 + w], -1))
            marks0 = marks0 | marks_of(jnp.concatenate(named), ntot)

        # Mailbox edges: Ref (and Blob) arguments of queued
        # messages. A row that holds a message is a root, so these
        # are marked ONCE, here; and only the occupied slots are
        # read: rank k of every mailbox at a time (ring slot
        # (head + k) % cap where k < occupancy; state.ring_take, a
        # select chain over the planar [cap, w1_c, rows] table), as
        # deep as the fullest mailbox. ONE walk serves both masks
        # (ref args feed the actor trace, Blob args the blob sweep).
        walk_blobs = sweep_blobs and any_blob_args
        walked = [c for c in program.cohorts
                  if st.buf[c.atype.__name__].shape[1] > 1] \
            if any_ref_args or walk_blobs else []
        mb_blobs = jnp.zeros((bsl if walk_blobs else 0,), jnp.bool_)
        if walked:
            def rank_k(carry):
                k, marks, bmarks = carry
                refs = []
                for cohort in walked:
                    cbuf = st.buf[cohort.atype.__name__]
                    s0, s1 = cohort.local_start, cohort.local_stop
                    msg = ring_take(cbuf, (st.head[s0:s1] + k)
                                    % cohort.mailbox_cap)
                    held = k < occ[s0:s1]
                    own = [b.global_id for b in cohort.behaviours]
                    for w in range(cbuf.shape[1] - 1):
                        rm = _carries(ref_mask_np, w, msg[0], own)
                        if rm is not None:
                            refs.append(jnp.where(rm & held, msg[1 + w], -1))
                        bm = _carries(blob_mask_np, w, msg[0], own) \
                            if walk_blobs else None
                        if bm is not None:
                            bmarks = bmark(bmarks, msg[1 + w], bm & held)
                if refs:
                    marks = marks | marks_of(jnp.concatenate(refs),
                                             ntot)
                return k + 1, marks, bmarks

            deepest = jnp.max(occ)
            _, marks0, mb_blobs = lax.while_loop(
                lambda c: c[0] < deepest, rank_k,
                (jnp.int32(0), marks0, mb_blobs))

        # State-field edges (targets are global ids; sources local
        # rows), every Ref field of every device cohort in one list,
        # sorted by target once.
        srcs, oks, tgts = [], [], []
        for cohort in program.device_cohorts:
            s0, s1 = cohort.local_start, cohort.local_stop
            for fname in _ref_fields(cohort):
                col = st.type_state[cohort.atype.__name__][fname]
                srcs.append(jnp.arange(s0, s1, dtype=jnp.int32))
                oks.append(st.alive[s0:s1])
                tgts.append(col.astype(jnp.int32))
        if srcs:
            src_sorted, below = sorted_edges(
                jnp.concatenate(srcs), jnp.concatenate(oks),
                jnp.concatenate(tgts), ntot)

        phase("gc_mark/hop")

        def glob(marks):
            if p > 1:
                marks = lax.psum(marks.astype(jnp.int32), "actors") > 0
                return lax.dynamic_slice(marks, (base,), (nl,))
            return marks

        live0 = glob(marks0)

        def cond(carry):
            _, changed, it = carry
            going = changed
            if max_iters:
                going = going & (it < max_iters)
            return going

        def body(carry):
            """One hop: mark every target a live source's field names."""
            live, _, it = carry
            with phase_scope("gc_mark/hop"):
                new_live = live
                if srcs:
                    new_live = live | glob(
                        hop_marks(live, src_sorted, below))
                ch = jnp.any(new_live != live)
                if p > 1:
                    ch = lax.psum(ch.astype(jnp.int32), "actors") > 0
            return new_live, ch, it + 1

        live, changed, iters = lax.while_loop(
            cond, body, (live0, jnp.bool_(True), jnp.int32(0)))
        converged = ~changed

        phase("gc_mark/sweep")
        # --- collect (only on a converged trace; ≙ cycle.c `collect`) ---
        free_before = jnp.sum((~st.alive & (rows < fh)).astype(jnp.int32))
        dead = st.alive & ~live & (rows < fh) & converged
        n_dead = jnp.sum(dead.astype(jnp.int32))

        # --- blob sweep (≙ an actor's heap dying with it, gc.c/heap.c):
        # a pool slot stays allocated iff a surviving actor's Blob FIELD
        # holds it, a queued/spilled message's Blob ARG carries it, or
        # the host declared it a root (rt.blob_store handles not yet
        # sent). Marking is shard-LOCAL on purpose: migration
        # (route._route) re-homes a payload WITH its routed message,
        # so every resting reachable handle is local to its pool's
        # shard; the rare off-shard handle (host injection without
        # near=, migration drop) is undereferenceable and collects.
        n_swept = jnp.int32(0)
        blob_used2, blob_len2 = st.blob_used, st.blob_len
        nbf2 = st.n_blob_free
        if sweep_blobs:
            alive2 = st.alive & ~dead

            bm = blob_roots
            for cohort in program.device_cohorts:
                s0, s1 = cohort.local_start, cohort.local_stop
                for fname in _blob_fields(cohort):
                    col = st.type_state[cohort.atype.__name__][fname]
                    bm = bmark(bm, col.astype(jnp.int32), alive2[s0:s1])
            if any_blob_args:
                for tgt_arr, words_arr in (
                        (st.dspill_tgt, st.dspill_words),
                        (st.rspill_tgt, st.rspill_words)):
                    for w in range(min(words_arr.shape[0] - 1,
                                       opts.msg_words)):
                        carried = _carries(blob_mask_np, w, words_arr[0])
                        if carried is not None:
                            bm = bmark(bm, words_arr[1 + w],
                                       carried & (tgt_arr >= 0))
                # Queued-message handles: marked by the shared mailbox
                # walk above.
                bm = bm | mb_blobs
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
            route_counts=st.route_counts,
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
        born = st.n_spawned[0]
        if p > 1:
            n_dead, n_swept, free_before, born = lax.psum(
                (n_dead, n_swept, free_before, born), "actors")
        return st2, (n_dead, converged, iters, n_swept, free_before,
                     born)

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
        out_specs=(state_spec, (repl,) * 6),
        check_vma=False)
    return jax.jit(mapped, donate_argnums=(0,))
