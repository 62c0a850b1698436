"""Throughput mode for a world that lives in backpressure.

The window is `throughput`'s: the same segments (`run(max_steps=K)` back
to back, each followed by a read of `n_processed`), the same K and the
same `msgs_per_s`, the median of the segments' rates — imported, not
copied. What differs is what `correct` means. In mode `throughput` a
capacity rejection is an error and every message sits in a ring; here
rejections, the spill, mutes and unmutes are the protocol at work, so
`finish` holds the world to the fan-in's own references
(`reference_fanin.py`):

  reference_first_ticks   the warm-up's first ticks equal the protocol
                          written down tick by tick, on every actor:
                          items sent and muted per producer; items
                          counted, their sequence sum, queued and
                          spilled per aggregator;
  conservation            after the last tick, for every aggregator:
                          counted + queued + spilled = sent by its
                          producers, in number and in sequence sum;
                          every producer holds its one `produce`; nobody
                          is muted behind a drained aggregator.

`n_rejected` is read as a measure (`spill_carried`), not as an error.
The counters this mode adds to the window's record are read outside the
segments' clock.

A world for this mode offers: `rt`, `live`, `counts()`, `observed()` and
`reference(ticks)` (the same keys), `conservation()`, `held()`, `p`
(producers).
"""

from __future__ import annotations

import numpy as np

from benchmarks.modes import throughput
from benchmarks.modes.throughput import MASK32, traced  # noqa: F401

PROTOCOL_COUNTERS = ("n_rejected", "n_mutes", "n_delivered")
ERROR_COUNTERS = ("n_badmsg", "n_deadletter")
KEYS = ("sent", "muted", "total", "seq_sum", "queued", "spilled")


class _FirstTicks:
    """What `throughput.warm_up` compares, widened from the behaviours
    each actor ran to everything the protocol lets one observe."""

    def __init__(self, world):
        self.rt, self._world = world.rt, world

    @staticmethod
    def _flat(state: dict) -> np.ndarray:
        return np.concatenate([np.asarray(state[k], np.int64) for k in KEYS])

    def counts(self) -> np.ndarray:
        return self._flat(self._world.observed())

    def reference(self, ticks: int) -> np.ndarray:
        return self._flat(self._world.reference(ticks))


def _protocol_counts(rt) -> dict:
    return {c: rt.counter(c) & MASK32 for c in PROTOCOL_COUNTERS}


def warm_up(world, traffic: dict, seconds: float) -> dict:
    """`throughput`'s warm-up (compile, first ticks against the
    reference, K, one segment of K ticks), comparing everything the
    protocol lets one observe."""
    return throughput.warm_up(_FirstTicks(world), traffic, seconds)


def window(world, plan: dict, seconds: float) -> dict:
    """The timed window, and beside it what the protocol did in it."""
    before = _protocol_counts(world.rt)
    win = throughput.window(world, plan, seconds)
    after = _protocol_counts(world.rt)
    win["protocol"] = {c: (after[c] - before[c]) & MASK32 for c in after}
    st = world.rt.state
    win["spill_entries"] = int(np.asarray(st.dspill_count).sum())
    win["spill_cap"] = int(world.rt.opts.spill_cap)
    win["muted_producers"] = int(world.observed()["muted"].sum())
    win["producers"] = int(world.p)
    return win


def finish(world, plan: dict, win: dict, extra: dict | None) -> dict:
    rt = world.rt
    counts = world.counts()
    errors = {c: rt.counter(c) for c in ERROR_COUNTERS}
    overflowed = bool(np.asarray(rt.state.spill_overflow).any())
    kept = world.conservation()
    bad_codes = win["bad_codes"] + (extra["bad_codes"] if extra else 0) \
        + sum(c != 0 for c in plan["codes"])
    checks = {
        "reference_first_ticks": plan["reference_ok"],
        "run_returned_0": bad_codes == 0,
        **kept["checks"],
        "no_dead_letter_or_bad_message": not any(errors.values()),
        "no_spill_overflow": not overflowed,
        # every behaviour the device counted, some actor counted too
        "counts_sum_is_n_processed":
        int(counts.sum()) & MASK32 == rt.counter("n_processed") & MASK32,
    }
    held = world.held()
    rates = np.asarray(win["segment_dispatched"]) / np.asarray(win["segment_s"])
    return {
        "metrics": {"msgs_per_s": float(np.median(rates))},
        "attempted": win["dispatched"] + held,
        "failed": (kept["deficit"] + sum(errors.values()) + int(overflowed)
                   + bad_codes),
        "checks": checks,
        "notes": {"k": plan["k"], "ticks_in_window": win["ticks"],
                  "segments": win["segments"], "held": held,
                  "mean_msgs_per_s": win["dispatched"] / win["wall_s"],
                  "segment_s": [round(x, 4) for x in win["segment_s"]],
                  "segment_dispatched": win["segment_dispatched"],
                  **win["protocol"], "spill_entries": win["spill_entries"],
                  "muted_producers": win["muted_producers"], **errors},
    }
