"""Throughput mode for a world whose behaviours update a table held in
the actors' heaps (the device blob pool).

The window is `throughput`'s: the same segments (`run(max_steps=K)` back
to back, each followed by a read of `n_processed`), the same K and the
same `msgs_per_s`, the median of the segments' rates — imported, not
copied. What differs is what `correct` means. In mode `throughput` the
world holds a fixed number of messages and every actor's count has a
closed form; here the queued updates move from tick to tick and which
same-tick arrival lands first is the delivery's business, so the world
is held to the order-free invariant of `reference_gups.py`, on every
table word and every updater, read from the chip's own state:

  invariant_first_ticks   after the warm-up's first ticks at full size;
  invariant_every_word,   after the last tick: table ^ queued datums =
  invariant_every_updater i ^ generated datums; applied + queued =
                          generated; every streamer's generator is the
                          reference's after its `done` dispatches and it
                          holds its one `apply`.

Both checks are outside the segments' clock (the first inside set-up).
A capacity rejection, a failed or remote blob access, a dead letter or
a bad message is an error here; the table is built once, at set-up:
`n_blob_alloc` reads the updaters then and after the last tick.

A world for this mode offers: `rt`, `live`, `counts()`, `check()`,
`held()`, `errors()`, `u` (updaters), `blobs_at_setup`.
"""

from __future__ import annotations

import numpy as np

from benchmarks.modes import throughput
from benchmarks.modes.throughput import (MASK32, traced,  # noqa: F401
                                         window)

OFF = ("words_off", "updaters_off")


class _FirstTicks:
    """What `throughput.warm_up` compares, turned from the behaviours
    each actor ran into how far the system is off the invariant: words,
    updaters and failed checks, against none."""

    def __init__(self, world):
        self.rt, self._world = world.rt, world

    def counts(self) -> np.ndarray:
        found = self._world.check()
        self._seen = np.array(
            [found[k] for k in OFF]
            + [not ok for ok in found["checks"].values()], np.int64)
        return self._seen

    def reference(self, ticks: int) -> np.ndarray:
        return np.zeros_like(self._seen)


def warm_up(world, traffic: dict, seconds: float) -> dict:
    """`throughput`'s warm-up (compile, first ticks against the
    reference, K, one segment of K ticks), the comparison being the
    invariant on the whole table."""
    return throughput.warm_up(_FirstTicks(world), traffic, seconds)


def finish(world, plan: dict, win: dict, extra: dict | None) -> dict:
    rt = world.rt
    counts = world.counts()
    errors = world.errors()
    kept = world.check()
    blobs = rt.counter("n_blob_alloc")
    bad_codes = win["bad_codes"] + (extra["bad_codes"] if extra else 0) \
        + sum(c != 0 for c in plan["codes"])
    checks = {
        "invariant_first_ticks": plan["reference_ok"],
        "run_returned_0": bad_codes == 0,
        **kept["checks"],
        "error_counters_zero": not any(errors.values()),
        "table_built_once_at_setup":
        world.blobs_at_setup == world.u == blobs,
        # every behaviour the device counted, some actor counted too
        "counts_sum_is_n_processed":
        int(counts.sum()) & MASK32 == rt.counter("n_processed") & MASK32,
    }
    held = world.held()
    rates = np.asarray(win["segment_dispatched"]) / np.asarray(win["segment_s"])
    return {
        "metrics": {"msgs_per_s": float(np.median(rates))},
        "attempted": win["dispatched"] + held,
        "failed": (sum(kept[k] for k in OFF) + sum(errors.values())
                   + bad_codes),
        "checks": checks,
        "notes": {"k": plan["k"], "ticks_in_window": win["ticks"],
                  "segments": win["segments"], "held": held,
                  "mean_msgs_per_s": win["dispatched"] / win["wall_s"],
                  "segment_s": [round(x, 4) for x in win["segment_s"]],
                  "n_blob_alloc": blobs, **errors},
    }
