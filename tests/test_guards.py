"""Three guards of the tick are decided per ROW or at trace time, never
per entry of the delivery list: the level of an incoming send (a
constant of the program, or of each outbox), "does anything target a
pressured actor" (asked of the per-row counts) and the target's
liveness (a dead row's segment is taken out where the counts are).

`deliver` is held here to the semantics the per-entry guards had,
written out in NumPy: a send to a dead row is counted once, never
delivered, never spilled and never mutes its sender, with and without
the pressure branch running. The plan leaves are the one thing that may
differ from those semantics, and only on a tick that carries a send to a
dead row: it sorts inside its row's segment, not after the last row.

Inside the pressure branch and the unmute pass, what the mute protocol
asks of a ROW is decided over the rows first, and an index vector reads
each answer once (the tick's jaxpr, below).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ponyc_tpu import I32, Ref, Runtime, RuntimeOptions, actor, behaviour
from ponyc_tpu.models import ring
from ponyc_tpu.program import Program
from ponyc_tpu.runtime import engine
from ponyc_tpu.runtime.delivery import Entries, deliver
from ponyc_tpu.runtime.state import init_state, layout_sizes

N, E, W1, LEVELS, SLOTS = 12, 48, 2, 3, 4
HOT, WARM, BRIM, DEAD_SOME = 3, 5, 1, (7, 9)
# `rejected-crowded`: two full rows that share mute slot 2 of SLOTS, one
# sender of both, and a sender of HOT and WARM that declared pressure
CLASH, CLASH_SENDER, DECLARED_SENDER = (6, 10), 0, 2
LAYOUT = [("A", 0, N, W1)]


def _case(cap, dead, pressure, seed=0, base=0):
    """One tick's inputs. Row HOT takes 5 sends, row WARM 3, row BRIM
    one and DEAD_SOME two each; the rest are empty slots, out-of-range
    targets and a sprinkle over the other rows. Under `rejected` HOT and
    WARM have room for fewer than they are sent, and BRIM sits at the
    overload threshold: one accepted send makes it hot, none is
    rejected. `rejected-crowded` adds the two full CLASH rows, whose
    refs share one mute slot of CLASH_SENDER, and DECLARED_SENDER's
    own pressure. The shard's rows are the global ids from `base`."""
    rng = np.random.default_rng(seed)
    others = [r for r in range(N) if r not in (HOT, WARM, BRIM)]
    rest = E - 5 - 3 - 1 - 4
    draw = rng.random(rest)
    tgt = np.concatenate([
        np.full(5, HOT), np.full(3, WARM), [BRIM], np.repeat(DEAD_SOME, 2),
        np.where(draw < 0.5, -1,
                 np.where(draw < 0.6, N, rng.choice(others, rest)))])
    # -1 the host, >= N another shard's actor; HOT and WARM send too (an
    # overloaded sender is exempt from muting)
    sender = np.concatenate([
        [0, 2, N + 1, -1, WARM], [2, 4, HOT], [6], [8, 10, 0, WARM],
        rng.integers(-1, N + 3, rest)])
    crowded = pressure == "rejected-crowded"
    if crowded:
        tgt[13:17] = (*CLASH, CLASH[0], CLASH[1])
        sender[13:17] = (CLASH_SENDER, CLASH_SENDER, 4, N + 1)
    # A shard that does not start the id space: a sender is local when
    # its global id is in [base, base + N). Some of the sprinkle keeps
    # a small id, which is then an actor of a shard before this one.
    far = np.concatenate([np.zeros(E - rest, bool), rng.random(rest) < 0.3])
    far[6] = True                            # 4 -> WARM
    sender = np.where((sender >= 0) & ~far, sender + base, sender)
    mix = rng.permutation(E)
    tgt, sender = tgt[mix], sender[mix]
    words = rng.integers(1, 1 << 20, (W1, E))
    level = rng.integers(0, LEVELS, E)
    occ = np.zeros(N, np.int64)
    pressured = np.zeros(N, bool)
    if pressure.startswith("rejected"):
        occ[HOT], occ[WARM], occ[BRIM] = cap - 3, cap - 2, cap * 3 // 4
    if crowded:
        occ[list(CLASH)] = cap
        pressured[DECLARED_SENDER] = True
    elif pressure == "declared-targeted":
        pressured[[WARM, DEAD_SOME[0]]] = True
    elif pressure == "declared-untargeted":
        free = [r for r in others if not (tgt == r).any()]
        assert free, "no untargeted row in this draw"
        pressured[free] = True
    alive = np.ones(N, bool)
    if dead == "some":
        alive[list(DEAD_SOME)] = False
    elif dead == "hottest":
        alive[HOT] = False
    head = rng.integers(0, 1 << 10, N)
    buf = rng.integers(-9, 0, (cap, W1, N))
    return dict(buf=buf, head=head, tail=head + occ, alive=alive,
                pressured=pressured, tgt=tgt, sender=sender, words=words,
                level=level, base=base)


def _reference(cap, overload, c):
    """What the per-entry guards decided: liveness asked of every entry
    (`alive[tgt]`), pressure asked of every entry (`pressured[tgt]`),
    and of every entry's sender whether it lives on this shard, is over
    the overload threshold or declared pressure itself."""
    tgt, alive, pressured = c["tgt"], c["alive"], c["pressured"]
    base = c["base"]
    sender = c["sender"] - base              # a local sender's row
    in_range = (tgt >= 0) & (tgt < N)
    valid = in_range & alive[np.clip(tgt, 0, N - 1)]
    key = np.where(valid, tgt * LEVELS + c["level"], N * LEVELS)
    order = [i for i in np.argsort(key, kind="stable") if valid[i]]
    occ = c["tail"] - c["head"]
    buf, tail = c["buf"].copy(), c["tail"].copy()
    rejected, seen = [], np.zeros(N, np.int64)
    for i in order:                      # by target, level, arrival
        r = tgt[i]
        if occ[r] + seen[r] < cap:
            buf[tail[r] % cap, :, r] = c["words"][:, i]
            tail[r] += 1
        else:
            rejected.append(i)
        seen[r] += 1
    occ_after = tail - c["head"]
    hot = (occ_after > overload) | pressured
    targeted = np.bincount(tgt[valid], minlength=N) > 0
    muted = np.zeros(N, bool)
    refs = np.full((SLOTS, N), -1)
    ovf = np.zeros(N, bool)
    triggers = [i for i in order if i in rejected or hot[tgt[i]]]
    if rejected or (occ_after > overload).any() \
            or (pressured & targeted).any():
        for i in triggers:
            s = sender[i]
            if 0 <= s < N and not hot[s]:
                muted[s] = True
                ref = tgt[i] + base          # a muter's global id
                slot = ref % SLOTS
                ovf[s] |= refs[slot, s] not in (-1, ref)
                refs[slot, s] = max(refs[slot, s], ref)
    spill = np.full((2 + W1, E), -1)
    spill[2:] = 0
    for j, i in enumerate(rejected):
        spill[:, j] = (tgt[i], c["sender"][i], *c["words"][:, i])
    return dict(buf=buf, tail=tail, spill=spill, muted=muted, refs=refs,
                ovf=ovf, triggers=triggers, n_delivered=len(order) - len(rejected),
                n_rejected=len(rejected),
                n_deadletter=int((in_range & ~valid).sum()))


def _run(cap, overload, cosort):
    def tick(c, plan):
        i32 = lambda a: jnp.asarray(a, jnp.int32)
        return deliver(
            {"A": i32(c["buf"])}, i32(c["head"]), i32(c["tail"]),
            jnp.asarray(c["alive"]),
            Entries(i32(c["tgt"]), i32(c["sender"]), i32(c["words"])),
            n_local=N, mailbox_cap=cap, spill_cap=E, overload_occ=overload,
            shard_base=jnp.int32(c["base"]), cohort_layout=LAYOUT,
            mute_slots=SLOTS, level=i32(c["level"]), n_levels=LEVELS,
            plan=plan, pressured=jnp.asarray(c["pressured"]),
            cosort=cosort)
    return jax.jit(tick)


_jitted = {}

DEAD = ["none", "some", "hottest"]
PRESSURE = ["none", "rejected", "rejected-crowded", "declared-targeted",
            "declared-untargeted"]


@pytest.mark.parametrize("base", [0, 100], ids=lambda v: f"base{v}")
@pytest.mark.parametrize("cap", [8, 64], ids=lambda v: f"cap{v}")
@pytest.mark.parametrize("pressure", PRESSURE)
@pytest.mark.parametrize("dead", DEAD, ids=lambda v: f"dead-{v}")
@pytest.mark.parametrize("mode", ["plan", "cosort"])
def test_deliver_keeps_the_per_entry_guards_semantics(mode, dead, pressure,
                                                      cap, base):
    overload = cap * 3 // 4
    c = _case(cap, dead, pressure, base=base)
    want = _reference(cap, overload, c)
    # the case is the one its name says
    assert (want["n_deadletter"] > 0) == (dead != "none")
    assert (want["n_rejected"] > 0) == pressure.startswith("rejected")
    assert want["muted"].any() == (pressure in (
        "rejected", "rejected-crowded", "declared-targeted"))
    if pressure.startswith("rejected"):
        # WARM is sent more than it has room for; HOT sends to it and,
        # filled over the threshold itself, is not muted for it. Where
        # HOT is dead, what it is sent beyond its room is not rejected
        # (and the table `hot_s` reads is the dead row's as it is).
        assert (want["spill"][0] == HOT).any() == (dead != "hottest")
        assert not want["muted"][HOT] or dead == "hottest"
    if pressure == "rejected-crowded":
        # both CLASH rows muted their common sender, in one slot; the
        # sender that declared pressure is exempt, and only for that
        assert want["ovf"][CLASH_SENDER]
        assert want["refs"][CLASH[0] % SLOTS, CLASH_SENDER] \
            == CLASH[1] + base
        assert not want["muted"][DECLARED_SENDER]
        undeclared = dict(c, pressured=np.zeros(N, bool))
        assert _reference(cap, overload, undeclared)["muted"][DECLARED_SENDER]
    if base and want["muted"].any():
        # a sender of a shard before this one sent to a row that mutes
        assert any(0 <= c["sender"][i] < N for i in want["triggers"])

    run = _jitted.setdefault((mode, cap), _run(cap, overload,
                                               mode == "cosort"))
    stale = (jnp.full((E,), -1, jnp.int32), jnp.zeros((E,), jnp.int32),
             jnp.zeros((N + 1,), jnp.int32))
    miss = run(c, stale)
    hit = run(c, (miss.plan_key, miss.plan_perm, miss.plan_bounds))
    for res in (miss, hit):
        np.testing.assert_array_equal(res.buf["A"], want["buf"])
        np.testing.assert_array_equal(res.tail, want["tail"])
        np.testing.assert_array_equal(
            np.concatenate([res.spill.tgt[None], res.spill.sender[None],
                            res.spill.words]), want["spill"])
        np.testing.assert_array_equal(res.newly_muted, want["muted"])
        np.testing.assert_array_equal(res.new_mute_refs, want["refs"])
        np.testing.assert_array_equal(res.new_mute_ovf, want["ovf"])
        got = {k: int(getattr(res, k))
               for k in ("n_delivered", "n_rejected", "n_deadletter")}
        assert got == {k: want[k] for k in got}
        assert int(res.spill_count) == want["n_rejected"]
        assert not bool(res.spill_overflow)
    if mode == "plan":
        # The plan is the sort of (target, level) of every in-range
        # entry: the per-entry guard's plan exactly when no target is
        # dead, and a dead row's sends inside its segment otherwise.
        in_range = (c["tgt"] >= 0) & (c["tgt"] < N)
        key = np.where(in_range, c["tgt"] * LEVELS + c["level"], N * LEVELS)
        np.testing.assert_array_equal(miss.plan_key, key)
        np.testing.assert_array_equal(miss.plan_perm,
                                      np.argsort(key, kind="stable"))
        np.testing.assert_array_equal(hit.plan_perm, miss.plan_perm)
        np.testing.assert_array_equal(hit.plan_bounds, miss.plan_bounds)


@pytest.mark.parametrize("mode", ["plan", "cosort"])
def test_a_tick_of_dead_letters_only_counts_them_and_delivers_nothing(mode):
    """`any(in_range)` opens the tick: sends that all go to dead rows
    are counted, and every table comes back as it went in — under an
    unrelated overloaded row too, where the pressure branch then runs
    over a list it must find nothing in."""
    cap = 8
    c = _case(cap, "hottest", "none")
    c["tgt"] = np.where(c["tgt"] == HOT, HOT, -1)
    c["tail"][WARM] += cap                      # overloaded, untargeted
    want = _reference(cap, cap * 3 // 4, c)
    assert want["n_deadletter"] == 5 and want["n_delivered"] == 0
    res = _run(cap, cap * 3 // 4, mode == "cosort")(c, None)
    np.testing.assert_array_equal(res.buf["A"], c["buf"])
    np.testing.assert_array_equal(res.tail, c["tail"])
    assert int(res.n_deadletter) == 5
    assert int(res.n_delivered) == int(res.n_rejected) == 0
    assert not np.asarray(res.newly_muted).any()
    assert (np.asarray(res.spill.tgt) == -1).all()
    assert (np.asarray(res.new_mute_refs) == -1).all()


# ------------------------------------------------- the tick's jaxpr

@actor
class Urgent:
    PRIORITY = 1
    MAX_SENDS = 1
    sink: Ref

    @behaviour
    def go(self, st, v: I32):
        self.send(st["sink"], ring.RingNode.token, v)
        return st


def _one_priority(prog):
    prog.declare(ring.RingNode, 24)


def _two_priorities(prog):
    prog.declare(ring.RingNode, 16)
    prog.declare(Urgent, 8)


def _row_table_gathers(declare, mode):
    """(scope, table dtype, index shape) of every `gather` of one tick
    that reads an [n_local] table, and the sizes that tell an index
    vector from a table: (n_local, outbox entries, mute slots)."""
    opts = RuntimeOptions(mailbox_cap=8, batch=2, max_sends=1, msg_words=1,
                          spill_cap=32, inject_slots=4, delivery=mode,
                          compile_cache="off", tuning_cache="off")
    prog = Program(opts)
    declare(prog)
    prog.finalize()
    nl = prog.n_local
    e_out, _bucket, _entries = layout_sizes(prog, opts)
    assert e_out > nl                    # an index vector is not a table
    step = engine.build_step(prog, opts)
    k = opts.inject_slots
    jaxpr = jax.make_jaxpr(step)(
        init_state(prog, opts), jnp.full((k,), -1, jnp.int32),
        jnp.zeros((1 + opts.msg_words, k), jnp.int32))
    found = []

    def walk(jp, inherited):
        # A sub-jaxpr's name stacks are relative to its equation's.
        for eqn in jp.eqns:
            own = str(eqn.source_info.name_stack)
            scope = (own[own.rindex("pony/") + 5:] if "pony/" in own
                     else inherited)
            if eqn.primitive.name == "gather":
                table, idx = (v.aval for v in eqn.invars[:2])
                if table.shape == (nl,):
                    found.append((scope, table.dtype, idx.shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, scope)
    walk(jaxpr.jaxpr, "")
    return found, (nl, e_out, opts.mute_slots)


def _list_gathers(declare, mode):
    """(scope, indices) of every `gather` of one tick that reads an
    [n_local] table with at least an outbox of indices."""
    found, (_nl, e_out, _k) = _row_table_gathers(declare, mode)
    return [(scope, idx[0]) for scope, _dtype, idx in found
            if idx[0] >= e_out]


@pytest.mark.parametrize("mode", ["plan", "cosort"])
@pytest.mark.parametrize("declare", [_one_priority, _two_priorities],
                         ids=lambda f: f.__name__.strip("_"))
def test_no_guard_reads_the_list(declare, mode):
    """No per-entry read of a per-row table under `pony/route`, directly
    under `pony/delivery`, or under `pony/delivery/pressure` outside the
    branch (`/spill`, `/mute`, which do read `acc`, `occ_after` and
    `pressured` per entry: there the entries are what is decided). On
    one chip that holds for a program of several priorities too: an
    outbox's entries all carry their cohort's level
    (tests/test_priority.py holds the ordering)."""
    found = _list_gathers(declare, mode)
    guards = [f for f in found
              if f[0] in ("route", "delivery", "delivery/pressure")]
    assert guards == []
    # the walk does see per-entry reads where they belong
    assert {s for s, _n in found} >= {"delivery/pressure/spill",
                                      "delivery/pressure/mute"}


@pytest.mark.parametrize("mode", ["plan", "cosort"])
@pytest.mark.parametrize("declare", [_one_priority, _two_priorities],
                         ids=lambda f: f.__name__.strip("_"))
def test_the_mute_protocol_reads_one_word_a_row(declare, mode):
    """Inside the pressure branch the list reads an [n_local] table
    twice by the target index and once by the sender index, and on one
    shard the unmute pass reads one by its [K, n_local] refs. The
    UNPACKED form shipped: by the target, `bound` (int32, under
    `/spill`: the sorted position at which the row's rejected suffix
    begins) and the bool `hot_t` (under `/mute`); by the sender the
    bool `hot_s`; the refs read the int32 status word (`muter_bits`:
    live-congested, can-recover, recovered, pressured). The packed
    target word `(bound << 1) | hot_t` is equal on every leaf and was
    measured: the tick read slower with it on the v5e (PERF.md §6,
    PR 32)."""
    found, (nl, e_out, slots) = _row_table_gathers(declare, mode)
    by_list = [(scope, dtype) for scope, dtype, idx in found
               if idx[0] >= e_out]
    assert by_list == [("delivery/pressure/spill", jnp.int32),
                       ("delivery/pressure/mute", jnp.bool_),
                       ("delivery/pressure/mute", jnp.bool_)]
    by_refs = [(scope, dtype) for scope, dtype, idx in found
               if idx[:2] == (slots, nl)]
    assert by_refs == [("unmute", jnp.int32)]


@actor
class Patient:
    MAX_SENDS = 1
    sink: Ref

    @behaviour
    def go(self, st, v: I32):
        self.send(st["sink"], Sink.item, v)
        return st


@actor
class Hasty:
    PRIORITY = 1
    MAX_SENDS = 1
    sink: Ref

    @behaviour
    def go(self, st, v: I32):
        self.send(st["sink"], Sink.item, v)
        return st


@actor
class Sink:
    first: I32
    seen: I32

    @behaviour
    def item(self, st, v: I32):
        return {"first": jnp.where(st["seen"] == 0, v, st["first"]),
                "seen": st["seen"] + 1}


@pytest.mark.parametrize("mode", ["plan", "cosort"])
def test_a_mesh_still_asks_each_entry_for_its_senders_level(mode):
    """Across shards an entry arrives without its cohort's outbox round
    it, so the level is read per entry from the sender's row — the one
    per-entry guard left, for programs of several priorities only. The
    Patient's send is emitted first and the Hasty's lands first."""
    rt = Runtime(RuntimeOptions(mailbox_cap=4, batch=1, max_sends=1,
                                msg_words=1, spill_cap=16, inject_slots=4,
                                delivery=mode, mesh_shards=2,
                                compile_cache="off", tuning_cache="off"))
    rt.declare(Patient, 2).declare(Hasty, 2).declare(Sink, 2).start()
    sink = rt.spawn(Sink)
    slow = rt.spawn(Patient, sink=int(sink))
    fast = rt.spawn(Hasty, sink=int(sink))
    rt.send(slow, Patient.go, 100)
    rt.send(fast, Hasty.go, 1)
    assert rt.run(max_steps=20) == 0
    assert rt.state_of(sink) == {"first": 1, "seen": 2}
    rt.stop()
