"""A cohort's own mailbox capacity (`MAILBOX_CAP` on the actor class,
PR 44): a type that takes back a whole window of replies beside types
that see two or three messages. `RuntimeOptions.mailbox_cap` stays the
default; a cohort that states its own gets its ring at that depth and
the options' overload and unmute FRACTIONS of it."""

import numpy as np
import pytest

from ponyc_tpu import (I32, Ref, Runtime, RuntimeOptions, actor, behaviour,
                       serialise)

BURST = 12


def _checks_order(st, seq):
    """`take(seq)`'s state: sequence numbers must arrive 0, 1, 2, ..."""
    return {**st, "bad": st["bad"] + (seq != st["got"]),
            "got": st["got"] + 1}


@actor
class Narrow:
    got: I32
    bad: I32

    BATCH = 1
    MAILBOX_CAP = 8

    @behaviour
    def take(self, st, seq: I32):
        return _checks_order(st, seq)


@actor
class Wide:
    got: I32
    bad: I32

    BATCH = 1           # no MAILBOX_CAP: RuntimeOptions' 64

    @behaviour
    def take(self, st, seq: I32):
        return _checks_order(st, seq)


@actor
class Pump:
    """`burst(narrow, wide)`: twelve numbered messages to each, in ONE
    dispatch: four more than the narrow ring holds."""
    sent: I32

    MAX_SENDS = 2 * BURST

    @behaviour
    def burst(self, st, narrow: Ref, wide: Ref):
        for seq in range(BURST):
            self.send(narrow, Narrow.take, seq)
            self.send(wide, Wide.take, seq)
        return {**st, "sent": st["sent"] + BURST}


def _world(mailbox_cap=64, **kw):
    opts = RuntimeOptions(mailbox_cap=mailbox_cap, batch=2, msg_words=2,
                          max_sends=1,
                          spill_cap=64, compile_cache="off",
                          tuning_cache="off", **kw)
    rt = Runtime(opts).declare(Pump, 2).declare(Narrow, 4).declare(Wide, 4)
    rt.start()
    return rt, (rt.spawn_many(Pump, 2), rt.spawn_many(Narrow, 4),
                rt.spawn_many(Wide, 4))


def test_each_cohort_has_its_own_ring_and_its_own_lines():
    rt, _ids = _world()
    rings = {t.__name__: (c.mailbox_cap, c.overload_occ, c.unmute_occ)
             for t, c in rt.program.by_type.items()}
    assert rings == {"Pump": (64, 48, 16), "Narrow": (8, 6, 2),
                     "Wide": (64, 48, 16)}
    assert {n: b.shape[0] for n, b in rt.state.buf.items()} \
        == {"Pump": 64, "Narrow": 8, "Wide": 64}
    rt.stop()
    with pytest.raises(ValueError, match="power of two"):
        @actor
        class Odd:
            x: I32
            MAILBOX_CAP = 12

            @behaviour
            def poke(self, st):
                return st
        Runtime(RuntimeOptions()).declare(Odd, 1).start()


def test_the_host_fills_each_ring_to_its_own_depth():
    """`bulk_send` writes a cohort's ring at that cohort's depth and
    refuses the message past it; the drained sequence is in order."""
    rt, (_p, narrow, wide) = _world()
    for seq in range(8):
        rt.bulk_send(narrow, Narrow.take, np.full(4, seq))
    with pytest.raises(RuntimeError, match="overflow"):
        rt.bulk_send(narrow, Narrow.take, np.full(4, 8))
    for seq in range(64):
        rt.bulk_send(wide, Wide.take, np.full(4, seq))
    with pytest.raises(RuntimeError, match="overflow"):
        rt.bulk_send(wide, Wide.take, np.full(4, 64))
    rt.check_invariants()
    assert rt.run() == 0
    for atype, n in ((Narrow, 8), (Wide, 64)):
        state = rt.cohort_state(atype)
        assert (state["got"] == n).all() and not state["bad"].any()
    rt.stop()


def test_a_burst_is_rejected_at_each_cohorts_own_line_and_keeps_fifo():
    """Twelve messages in one tick: the 8-slot ring takes eight and the
    rest go round through the spill, the 64-slot ring takes all twelve;
    both receivers see 0..11 in order, and the sender is muted by the
    narrow one alone."""
    rt, (pumps, narrow, wide) = _world()
    rt.send(int(pumps[0]), Pump.burst, int(narrow[1]), int(wide[2]))
    rt.run(max_steps=2)     # the send lands, then the burst
    depth = np.asarray(rt.state.tail) - np.asarray(rt.state.head)
    assert depth[narrow[1]] == 8 and depth[wide[2]] == BURST
    assert rt.counter("n_rejected") == BURST - 8
    assert rt.counter("n_mutes") == 1 and bool(rt.state.muted[pumps[0]])
    rt.check_invariants()
    assert rt.run() == 0
    for atype, ids, row in ((Narrow, narrow, 1), (Wide, wide, 2)):
        state = rt.cohort_state(atype)
        assert state["got"].tolist() == [BURST * (i == row) for i in range(4)]
        assert not state["bad"].any()
    assert not np.asarray(rt.state.muted).any()
    rt.stop()


@pytest.mark.parametrize("delivery", ["plan", "cosort"])
def test_both_formulations_deliver_into_rings_of_two_depths(delivery):
    rt, (pumps, narrow, wide) = _world(delivery=delivery)
    for p, n, w in ((0, 0, 3), (1, 3, 0)):
        rt.send(int(pumps[p]), Pump.burst, int(narrow[n]), int(wide[w]))
    assert rt.run() == 0
    for atype in (Narrow, Wide):
        state = rt.cohort_state(atype)
        assert sorted(state["got"].tolist()) == [0, 0, BURST, BURST]
        assert not state["bad"].any()
    assert rt.counter("n_rejected") >= 2 * (BURST - 8)
    rt.stop()


@pytest.mark.parametrize("ring", [64, 32], ids=["same", "relayout"])
def test_rings_of_two_depths_survive_a_checkpoint_and_restore(tmp_path, ring):
    """Saved with messages in both rings and in the spill; restored into
    a fresh runtime — the same geometry, or the default ring halved, so
    that every cohort is re-rung at its own old and new depth — it
    completes to the same answer, in order."""
    rt, (pumps, narrow, wide) = _world()
    rt.send(int(pumps[1]), Pump.burst, int(narrow[2]), int(wide[0]))
    rt.run(max_steps=3)
    assert rt.counter("n_rejected") > 0
    assert int(np.asarray(rt.state.dspill_count).sum()) > 0
    path = str(tmp_path / "w.npz")
    serialise.save(rt, path)
    rt.stop()

    rt2, _ids = _world(mailbox_cap=ring)
    serialise.restore(rt2, path)
    assert {n: b.shape[0] for n, b in rt2.state.buf.items()} \
        == {"Pump": ring, "Narrow": 8, "Wide": ring}
    assert rt2.run() == 0
    for atype, row in ((Narrow, 2), (Wide, 0)):
        state = rt2.cohort_state(atype)
        assert state["got"].tolist() == [BURST * (i == row) for i in range(4)]
        assert not state["bad"].any()
    rt2.stop()
