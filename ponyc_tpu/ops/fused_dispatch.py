"""Fused Pallas dispatch kernel: drain + behaviour + outbox in ONE pass.

≙ the whole of ponyint_actor_run's hot loop (src/libponyrt/actor/
actor.c:383-549) for one cohort — message pop, dispatch into the
behaviour body, and the send path's message construction — executed as
a single TPU kernel over lane blocks. This is the kernel BASELINE.json's
north star names ("actor state + mailboxes laid out struct-of-arrays in
HBM and behaviour dispatch run as a vmapped/Pallas kernel"): one grid
step pulls a [cap, w1, LB] mailbox tile and the cohort's state lanes
into VMEM ONCE, iterates the batch slots in-register, evaluates the
(traced, planar) behaviour body on the lanes, and writes the new state,
outbox planes and head advance — where the XLA path makes `batch`
separate select-chain passes over the mailbox block plus materialised
scan intermediates.

Eligibility (`refusal()` — a cohort the kernel cannot host is refused
at start(), never quietly run on the XLA path):
  - no SYNC-construction across the cohort's behaviours (its per-site
    field-value packaging is host-assembled). destroy(), error_int()
    AND device spawns ARE hosted: destroy/error flags ride out as lane
    planes exactly like exit, and spawns take reservation planes in /
    claim planes out with a per-lane used-counter walk (round 5).
    Multi-behaviour cohorts are fine: the kernel evaluates every
    behaviour on the lanes and selects per lane by message id, exactly
    like the XLA scan;
  - behaviour body uses only elementwise/lane ops. This is the API
    contract anyway — a behaviour describes ONE actor's reaction, so
    lane-crossing ops (reductions over the cohort) have no defined
    meaning in either formulation; under the fused kernel they would
    additionally see only their 1024-lane grid block. Not statically
    detectable, hence contract + documentation, like vmap's own
    semantics.

Gating: `RuntimeOptions.pallas_fused` (off by default: compiled by
Mosaic on the v5e and bit-identical to the XLA path there —
chip_smoke.py phase (d) — but not yet timed against it; interpret mode
exercises the kernel on CPU in the suite and is unreachable on a TPU).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import mailbox_kernel as mk

LANE_BLOCK = mk.LANE_BLOCK      # one lane block for both kernels


def refusal(cohort, opts, sync_init: bool) -> str | None:
    """Why the fused kernel cannot host `cohort` as asked, or None.
    destroy/error AND device spawns are hosted (reservation planes ride
    in, claim planes ride out — ≙ pony_create from a behaviour,
    actor.c:688-734); synchronous construction is not (its per-site
    field-value packaging is host-assembled), nor is the blob pool.
    `sync_init` is the trace-discovered fact (verify.behaviour_effects
    at start(), the engine's own probe at build)."""
    name = cohort.atype.__name__
    if opts.blob_slots > 0 and cohort.uses_blobs:
        return f"cohort {name}: uses the device blob pool"
    if sync_init:
        return f"cohort {name}: a behaviour constructs actors synchronously"
    return mk.refusal(cohort)


def _slim_branch(bdef, field_specs, field_dtypes, msg_words, ms, lanes,
                 spawn_sites=(), spawn_meta=None):
    """The planar behaviour evaluator for eligible cohorts: the SAME
    shared core as the XLA path (engine.eval_behaviour — one
    implementation, so the two formulations cannot drift), emitting
    exit/yield/destroy/error lane planes plus per-(target, site) spawn
    claim planes; only the sync-construction packaging eligibility
    excludes is absent."""

    def branch(st, payload, ids_vec, resv_k):
        from ..runtime.engine import eval_behaviour
        ctx, st2, tgts, words = eval_behaviour(
            bdef, st, payload, ids_vec, msg_words=msg_words,
            field_specs=field_specs, field_dtypes=field_dtypes,
            lanes=lanes, max_sends=ms, spawn_resv=resv_k,
            spawn_meta=spawn_meta)
        b = jnp.bool_
        bc = lambda v, d: jnp.broadcast_to(       # noqa: E731
            jnp.asarray(v, d), (lanes,))
        claims = []
        for tname, n in spawn_sites:
            got = [bc(g, jnp.int32)
                   for g in ctx.spawn_claims.get(tname, [])]
            got += [jnp.full((lanes,), -1, jnp.int32)] * (n - len(got))
            claims.append(got)
        return (st2, tgts, words,
                bc(ctx.exit_flag, b), bc(ctx.exit_code, jnp.int32),
                bc(ctx.yield_flag, b),
                bc(ctx.destroy_flag, b),
                bc(ctx.error_flag, b), bc(ctx.error_code, jnp.int32),
                bc(ctx.error_loc, jnp.int32),
                claims, bc(ctx.spawn_fail, b))

    return branch


def build_fused_dispatch(bdefs, *, base_gid: int, field_names: Sequence[str],
                         field_dtypes, field_specs, batch: int, cap: int,
                         msg_words: int, ms: int, rows: int,
                         noyield: bool, interpret: bool,
                         msg_words_in: int = None,
                         spawn_sites=(), spawn_meta=None,
                         spawn_dispatches: int = 1):
    """Returns fn(fields_tuple, buf, head, n_run, ids, resv_tuple) →
    (new_fields_tuple, out_tgt [batch*ms*rows], out_words [w1, b*ms*rows],
    new_head [rows], nproc [rows], nbad [rows], ef [rows], ec [rows],
    ds [rows], erf [rows], erc [rows], erl [rows],
    claims_tuple (per spawn target: [batch*sites, rows]), sfail [rows])
    with EXACTLY the XLA path's semantics (engine busy_fn ordering:
    entry (k, m, r) flattens k-major, then send slot, then lane; exit =
    first wins, error = latest wins, destroy ORs across the batch;
    spawn reservations walk the SPAWN_DISPATCHES axis by a per-lane
    `used` counter, exhausted budget → sticky spawn_fail).

    msg_words is the OUTBOX width (program-wide max); msg_words_in the
    cohort's own mailbox width (per-type pony_msg_t, genfun.c) — the
    mailbox tile read is [cap, 1+msg_words_in, LB]. resv_tuple holds,
    per spawn target (spawn_sites order), a [sd*sites, rows] int32
    reservation plane block."""
    if msg_words_in is None:
        msg_words_in = msg_words
    w1 = 1 + msg_words
    w1_in = 1 + msg_words_in
    sd = spawn_dispatches
    lb = min(LANE_BLOCK, rows)
    assert rows % lb == 0, (rows, lb)
    nf = len(field_names)
    n_sp = len(spawn_sites)
    branches = [_slim_branch(b, field_specs, field_dtypes, msg_words, ms,
                             lb, spawn_sites=spawn_sites,
                             spawn_meta=spawn_meta) for b in bdefs]
    nb = len(branches)

    def kernel(head_ref, nrun_ref, ids_ref, *refs):
        field_refs = refs[:nf]
        buf_ref = refs[nf]
        resv_refs = refs[nf + 1:nf + 1 + n_sp]
        o0 = nf + 1 + n_sp
        out_field_refs = refs[o0:o0 + nf]
        after = refs[o0 + nf:]
        # Output order MUST mirror out_specs: fields, outbox, claims,
        # then the lane planes.
        if ms:
            tgt_ref, words_ref = after[0], after[1]
            after = after[2:]
        else:                         # send-less cohort: no outbox planes
            tgt_ref = words_ref = None
        claims_refs = after[:n_sp]
        (nh_ref, np_ref, nb_ref, ef_ref, ec_ref, ds_ref, erf_ref,
         erc_ref, erl_ref, sf_ref) = after[n_sp:]
        head = head_ref[0]
        nrun = nrun_ref[0]
        ids = ids_ref[0]
        st = {name: field_refs[i][0]
              for i, name in enumerate(field_names)}
        stopped = jnp.zeros((lb,), jnp.bool_)
        ef = jnp.zeros((lb,), jnp.bool_)
        ec = jnp.zeros((lb,), jnp.int32)
        dstr = jnp.zeros((lb,), jnp.bool_)
        erf = jnp.zeros((lb,), jnp.bool_)
        erc = jnp.zeros((lb,), jnp.int32)
        erl = jnp.zeros((lb,), jnp.int32)
        sfail = jnp.zeros((lb,), jnp.bool_)
        used = jnp.zeros((lb,), jnp.int32)
        nproc = jnp.zeros((lb,), jnp.int32)
        nbad = jnp.zeros((lb,), jnp.int32)
        consumed = jnp.zeros((lb,), jnp.int32)
        for k in range(batch):
            slot = (head + k) % cap
            msg = buf_ref[0]                     # [w1_in, LB]
            for c in range(1, cap):
                msg = jnp.where((slot == c)[None, :], buf_ref[c], msg)
            valid = (nrun > k)
            do_any = valid & ~stopped
            local = msg[0] - base_gid
            in_range = (local >= 0) & (local < nb)
            do = do_any & in_range
            # This slot's spawn reservations: the `used` counter walks
            # the SPAWN_DISPATCHES axis exactly like the XLA scan —
            # exhausted budget yields -1 refs (sticky spawn_fail, never
            # a double claim).
            resv_k = {}
            for si, (tname, n_sites) in enumerate(spawn_sites):
                rr = resv_refs[si]               # [sd*sites, LB]
                sel = jnp.full((n_sites, lb), -1, jnp.int32)
                for d in range(sd):
                    blk = jnp.concatenate(
                        [rr[d * n_sites + s][None, :]
                         for s in range(n_sites)])
                    sel = jnp.where((used == d)[None, :], blk, sel)
                resv_k[tname] = sel
            # Evaluate every behaviour on the lanes, select per lane by
            # its message id — the same planar select the XLA scan does.
            acc_tgt = [jnp.full((lb,), -1, jnp.int32)
                       for _ in range(ms)]
            acc_words = [jnp.zeros((w1, lb), jnp.int32)
                         for _ in range(ms)]
            acc_claims = [[jnp.full((lb,), -1, jnp.int32)
                           for _ in range(n)] for _, n in spawn_sites]
            slot_sf = jnp.zeros((lb,), jnp.bool_)
            for j, branch in enumerate(branches):
                take = do & (local == j)
                (st2, tgts, words, bef, bec, byf, bds, berf, berc,
                 berl, bclm, bsf) = branch(st, msg[1:], ids, resv_k)
                for i, name in enumerate(field_names):
                    st[name] = jnp.where(take, st2[name], st[name])
                for m in range(ms):
                    acc_tgt[m] = jnp.where(take, tgts[m], acc_tgt[m])
                    acc_words[m] = jnp.where(take[None, :], words[m],
                                             acc_words[m])
                for si in range(n_sp):
                    for s in range(len(acc_claims[si])):
                        acc_claims[si][s] = jnp.where(
                            take, bclm[si][s], acc_claims[si][s])
                slot_sf = jnp.where(take, bsf, slot_sf)
                new_ef = take & bef
                ec = jnp.where(new_ef & ~ef, bec, ec)
                ef = ef | new_ef
                dstr = dstr | (take & bds)
                # Error: the LATEST error's code/loc wins (the XLA
                # scan's jnp.where(erf_n, ...) ordering).
                n_err = take & berf
                erc = jnp.where(n_err, berc, erc)
                erl = jnp.where(n_err, berl, erl)
                erf = erf | n_err
                if not noyield:
                    stopped = stopped | (take & byf)
            for m in range(ms):
                tgt_ref[k * ms + m] = acc_tgt[m]
                for w in range(w1):
                    words_ref[(k * ms + m) * w1 + w] = acc_words[m][w]
            # Claims out (plane k*sites+s ≙ the XLA [batch, sites, rows]
            # stack) + the used-counter walk (a failed WANTED spawn
            # advances the window too, like the scan's sf_n | claims).
            spawned = slot_sf
            for si in range(n_sp):
                n_sites = len(acc_claims[si])
                for s in range(n_sites):
                    claims_refs[si][k * n_sites + s] = acc_claims[si][s]
                    spawned = spawned | (acc_claims[si][s] >= 0)
            used = used + spawned.astype(jnp.int32)
            sfail = sfail | slot_sf
            nproc = nproc + do.astype(jnp.int32)
            nbad = nbad + (do_any & ~in_range).astype(jnp.int32)
            consumed = consumed + do_any.astype(jnp.int32)
        for i in range(nf):
            out_field_refs[i][0] = st[field_names[i]]
        nh_ref[0] = head + consumed
        np_ref[0] = nproc
        nb_ref[0] = nbad
        ef_ref[0] = ef.astype(jnp.int32)
        ec_ref[0] = ec
        ds_ref[0] = dstr.astype(jnp.int32)
        erf_ref[0] = erf.astype(jnp.int32)
        erc_ref[0] = erc
        erl_ref[0] = erl
        sf_ref[0] = sfail.astype(jnp.int32)

    @functools.partial(jax.jit)
    def run(fields, buf, head, n_run, ids, resv=()):
        grid = (rows // lb,)
        in_specs = (
            [pl.BlockSpec((1, lb), lambda i: (0, i))] * 3
            + [pl.BlockSpec((1, lb), lambda i: (0, i))] * nf
            + [pl.BlockSpec((cap, w1_in, lb), lambda i: (0, 0, i))]
            + [pl.BlockSpec((sd * n, lb), lambda i: (0, i))
               for _, n in spawn_sites])
        outbox_specs = ([pl.BlockSpec((batch * ms, lb),
                                      lambda i: (0, i)),
                         pl.BlockSpec((batch * ms * w1, lb),
                                      lambda i: (0, i))] if ms else [])
        outbox_shape = ([jax.ShapeDtypeStruct((batch * ms, rows),
                                              jnp.int32),
                         jax.ShapeDtypeStruct((batch * ms * w1, rows),
                                              jnp.int32)] if ms else [])
        claims_specs = [pl.BlockSpec((batch * n, lb), lambda i: (0, i))
                        for _, n in spawn_sites]
        claims_shape = [jax.ShapeDtypeStruct((batch * n, rows), jnp.int32)
                        for _, n in spawn_sites]
        out_specs = (
            [pl.BlockSpec((1, lb), lambda i: (0, i))] * nf
            + outbox_specs + claims_specs
            + [pl.BlockSpec((1, lb), lambda i: (0, i))] * 10)
        out_shape = (
            [jax.ShapeDtypeStruct((1, rows), fields[i].dtype)
             for i in range(nf)]
            + outbox_shape + claims_shape
            + [jax.ShapeDtypeStruct((1, rows), jnp.int32)] * 10)
        outs = pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, interpret=interpret,
            name="pony_dispatch",
        )(head[None, :], n_run[None, :], ids[None, :],
          *[f[None, :] for f in fields], buf, *resv)
        new_fields = tuple(outs[i][0] for i in range(nf))
        e = batch * ms * rows
        if ms:
            tgt = outs[nf]                   # [batch*ms, rows]
            words = outs[nf + 1]             # [batch*ms*w1, rows]
            after = outs[nf + 2:]
            # Flatten to the engine's entry order: (k, m, lane) with
            # lanes minor — words regroup to [w1, batch*ms*rows] planar.
            out_tgt = tgt.reshape(e)
            out_words = words.reshape(batch * ms, w1, rows)
            out_words = jnp.moveaxis(out_words, 1, 0).reshape(w1, e)
        else:
            after = outs[nf:]
            out_tgt = jnp.full((e,), -1, jnp.int32)
            out_words = jnp.zeros((w1, e), jnp.int32)
        claims_out = tuple(after[:n_sp])
        rest_out = after[n_sp:]
        (new_head, nproc, nbad, ef, ec, ds, erf, erc, erl, sf) = (
            o[0] for o in rest_out)
        return (new_fields, out_tgt, out_words, new_head, nproc, nbad,
                ef.astype(jnp.bool_), ec, ds.astype(jnp.bool_),
                erf.astype(jnp.bool_), erc, erl, claims_out,
                sf.astype(jnp.bool_))

    return run
