"""Throughput mode for the bank world: completed round trips a second.

The window is `throughput`'s: the same segments (`run(max_steps=K)` back
to back, each followed by one read from the device), the same K, the
median of the segments' rates — imported, not copied. It differs in two
things.

**What `msgs_per_s` counts.** Here it is 4 x (transactions completed in
the segment) / the segment's seconds: the protocol's own four messages a
transaction (credit, debit, reply, reply), messages set aside and sent
to self again left out. A segment ends in `throughput`'s read of the
one word `n_processed` and, here, of the tellers' `issued` and
`completed` columns (two of `banks` words). Not `n_processed`: in this
closed loop every message a bank has out is dispatched on every tick,
whether it moves its transaction or is set aside again, so
`n_processed` a tick is close to a constant and says nothing of the
protocol. Its rate is printed beside for people (`dispatched_per_s`).

**What `correct` means** (`reference_bank.py`):

  reference_first_ticks   the warm-up's first ticks equal the protocol
                          written down tick by tick, on every actor:
                          balance, reply mode, the sums in and out,
                          messages set aside, replies forwarded per
                          account; issued, completed and generator state
                          per teller; every mailbox's depth; who is
                          muted, and the mutes so far;
  invariant               after the last tick, from the chip's own
                          state: money conserved exactly, one transfer
                          at a time, every transaction one message;
  every segment boundary  `issued - completed` = what a teller has out,
                          and every teller's `completed` has grown;
  nothing refused         `n_rejected` and the spill are 0: a capacity
                          rejection is an error here, unlike the fan-in.

A mute is the protocol at work and is counted, not refused; where the
window left someone muted, one more tick (outside every clock) shows
that nobody stays muted behind a teller that had recovered.

A world for this mode offers: `rt`, `live`, `in_flight`, `banks`,
`tellers()`, `observed()` and `reference(ticks)` (the same keys),
`invariant(seen)`, `dispatches(seen)`.
"""

from __future__ import annotations

import numpy as np

from benchmarks import reference_bank as ref
from benchmarks.modes import throughput
from benchmarks.modes.throughput import MASK32

ERROR_COUNTERS = ("n_rejected", "n_badmsg", "n_deadletter")
PER_TRANSACTION = 4     # credit, debit, reply to the source, reply to the teller
KEYS = (*ref.ACCOUNT_FIELDS, *ref.TELLER_FIELDS, "teller_queued",
        "account_queued", "muted", "n_mutes")


class _Tellers:
    """The runtime as `throughput`'s segments see it, with the one
    counter they read answered by the protocol: `n_processed` is read
    (and kept, `dispatched`) and then 4 x the tellers' `completed` is
    returned (mod 2**32, as the device's counter wraps); the same read
    holds every boundary to the loop's own invariants."""

    def __init__(self, world):
        self._world, self._rt = world, world.rt
        self.boundaries = self.stalled = self.off_balance = 0
        self._completed = None
        self.dispatched = 0

    def __getattr__(self, name):
        return getattr(self._rt, name)

    def __repr__(self):
        return f"tellers' completed x {PER_TRANSACTION}"

    def counter(self, name: str) -> int:
        if name != "n_processed":
            return self._rt.counter(name)
        self.dispatched = self._rt.counter(name) & MASK32
        now = self._world.tellers()
        if self._completed is not None:
            self.boundaries += 1
            self.stalled += int((now["completed"] <= self._completed).sum())
            self.off_balance += int((now["issued"] - now["completed"]
                                     != self._world.in_flight).sum())
        self._completed = now["completed"]
        return PER_TRANSACTION * int(now["completed"].sum()) & MASK32


class _Protocol:
    """The world as `throughput` drives and compares it: its runtime is
    the tellers' clock, and what its warm-up compares is everything the
    protocol lets one observe."""

    def __init__(self, world, clock):
        self.rt, self.live, self._world = clock, world.live, world

    @staticmethod
    def _flat(seen: dict) -> np.ndarray:
        return np.concatenate([np.asarray(seen[k], np.int64).reshape(-1)
                               for k in KEYS])

    def counts(self) -> np.ndarray:
        return self._flat(self._world.observed())

    def reference(self, ticks: int) -> np.ndarray:
        return self._flat(self._world.reference(ticks))


def warm_up(world, traffic: dict, seconds: float) -> dict:
    """`throughput`'s warm-up (compile, first ticks against the
    reference, K, one segment of K ticks), comparing everything the
    protocol lets one observe. The seeding must be out before the first
    boundary is held to `issued - completed`."""
    clock = _Tellers(world)
    plan = throughput.warm_up(_Protocol(world, clock), traffic, seconds)
    seeding = -(-world.in_flight // world.teller_batch)
    if world.rt.steps_run < seeding:
        raise RuntimeError(f"the warm-up ran {world.rt.steps_run} ticks, "
                           f"the seeding takes {seeding}")
    clock.boundaries = clock.stalled = clock.off_balance = 0
    return {**plan, "clock": clock}


def window(world, plan: dict, seconds: float) -> dict:
    """The timed window, and beside it what the protocol did in it
    (read outside the segments' clock)."""
    rt, clock = world.rt, plan["clock"]
    before = {"n_processed": clock.dispatched,
              "n_mutes": rt.counter("n_mutes") & MASK32,
              "requeued": int(world.observed()["requeued"].sum())}
    win = throughput.window(_Protocol(world, clock), plan, seconds)
    seen = world.observed()
    win["protocol"] = {
        "n_processed": (clock.dispatched - before["n_processed"]) & MASK32,
        "n_mutes": (seen["n_mutes"] - before["n_mutes"]) & MASK32,
        "requeued": int(seen["requeued"].sum()) - before["requeued"]}
    win["teller_depth"] = float(seen["teller_queued"].mean())
    win["transactions"] = win["dispatched"] // PER_TRANSACTION
    win["in_flight"] = world.live
    return win


def traced(world, plan: dict, units: int) -> dict:
    """`units` more segments, for the profiler (the caller traces)."""
    return throughput.traced(_Protocol(world, plan["clock"]), plan, units)


def _stranded(world, seen: dict) -> int:
    """`reference_bank.stranded`: where the window left someone muted,
    run one more tick (outside every clock) and look again."""
    if not seen["muted"].any():
        return 0
    world.rt.run(max_steps=1)
    return ref.stranded(seen, world.observed(), accounts=world.a,
                        unmute_occ=world.ring(world.Teller)[2])


def finish(world, plan: dict, win: dict, extra: dict | None) -> dict:
    rt, clock = world.rt, plan["clock"]
    seen = world.observed()
    errors = {c: rt.counter(c) for c in ERROR_COUNTERS}
    st = rt.state
    spilled = int(np.asarray(st.dspill_count).sum()
                  + np.asarray(st.rspill_count).sum())
    overflowed = bool(np.asarray(st.spill_overflow).any())
    kept = world.invariant(seen)
    bad_codes = win["bad_codes"] + (extra["bad_codes"] if extra else 0) \
        + sum(c != 0 for c in plan["codes"])
    # every behaviour the device counted, some actor counted too
    counted = world.dispatches(seen) & MASK32 \
        == rt.counter("n_processed") & MASK32
    stranded = _stranded(world, seen)       # may run one more tick
    checks = {
        "reference_first_ticks": plan["reference_ok"],
        "run_returned_0": bad_codes == 0,
        **kept["checks"],
        "in_flight_at_every_boundary": clock.off_balance == 0,
        "progress_every_teller_every_segment":
        clock.boundaries > 0 and clock.stalled == 0,
        "nobody_stranded": stranded == 0,
        "nothing_rejected_or_lost": not any(errors.values()),
        "nothing_spilled": spilled == 0 and not overflowed,
        "counts_sum_is_n_processed": counted,
    }
    rates = np.asarray(win["segment_dispatched"]) / np.asarray(win["segment_s"])
    p = win["protocol"]
    return {
        "metrics": {"msgs_per_s": float(np.median(rates))},
        "attempted": win["dispatched"] + world.live,
        "failed": (kept["deficit"] + sum(errors.values()) + spilled
                   + int(overflowed) + bad_codes + clock.stalled
                   + clock.off_balance + stranded),
        "checks": checks,
        "notes": {"k": plan["k"], "ticks_in_window": win["ticks"],
                  "segments": win["segments"],
                  "transactions": win["transactions"],
                  "mean_msgs_per_s": win["dispatched"] / win["wall_s"],
                  "dispatched_per_s": p["n_processed"] / win["wall_s"],
                  "tx_per_tick": win["transactions"] / max(1, win["ticks"]),
                  "requeued": p["requeued"], "n_mutes": p["n_mutes"],
                  "muted_now": int(seen["muted"].sum()),
                  "teller_depth": win["teller_depth"],
                  "segment_s": [round(x, 4) for x in win["segment_s"]],
                  "segment_dispatched": win["segment_dispatched"],
                  **errors},
    }
