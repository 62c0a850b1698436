"""Throughput mode for the n-body world: visits dispatched a second.

The window is `throughput`'s: the same segments (`run(max_steps=K)` back
to back, each followed by the read of `n_processed`), the same K, the
median of the segments' rates — imported, not copied. `msgs_per_s`
counts what `n_processed` counts: every dispatch is one `visit`, the
source's force arithmetic on one pair.

It differs in what `correct` means: this is the first mode whose state
is floating point, so the first whose comparison carries tolerances.
Each is `reference_nbody.py`'s, with its reason there.

  reference_first_ticks   after the warm-up's first `reference_ticks`
                          ticks (32: 8 steps), at full size, every
                          body's `seen`, `step`, `bad` equal
                          `reference_nbody.Ticks`' exactly and its
                          position and velocity lie within `POS_TOL` /
                          `VEL_TOL` of Ticks', relative to the system's
                          largest: both sides float32 in one order;
  step boundary           after the last tick, brought to a multiple of
                          four outside every clock: every body's `step`
                          = ticks / 4, `seen` 0, `bad` 0 (no token
                          overtook another, on any dispatch of the run);
  energy, momentum        every system's energy, in float64 from the
                          chip's float32 state, within `ENERGY_TOL` of
                          the source's own pairwise `advance` in float64
                          after the same number of steps; its momentum
                          within `MOMENTUM_TOL` of zero;
  accounting              `n_processed` = ticks x bodies (mod 2**32),
                          every segment dispatched K x bodies, the world
                          holds one token a body, nothing rejected,
                          dead-lettered, spilled; `run()` returned 0.

A world for this mode offers: `rt`, `live`, `n`, `observed()` and
`reference(ticks)` (the same keys), `invariant(seen, ticks)`.
"""

from __future__ import annotations

import numpy as np

from benchmarks import reference_nbody as ref
from benchmarks.modes import throughput
from benchmarks.modes.common import error_counts
from benchmarks.modes.throughput import MASK32

window = throughput.window
traced = throughput.traced


class _Within:
    """The world as `throughput`'s warm-up compares it: what it counts
    is the bodies off the reference, by a counter or by a tolerance,
    and the reference of that is none. The comparison itself is kept
    (`first`) for the checks and the notes."""

    def __init__(self, world):
        self.rt, self.live, self._world = world.rt, world.live, world
        self.first = None

    def counts(self) -> np.ndarray:
        self.first = ref.compare(self._world.observed(),
                                 self._world.reference(self.rt.steps_run))
        return np.asarray(self.first["off"])

    def reference(self, _ticks: int) -> np.ndarray:
        return np.asarray(0)


def warm_up(world, traffic: dict, seconds: float) -> dict:
    """`throughput`'s warm-up (compile or reload the window, the first
    ticks against the reference, K, one segment of K ticks) with a
    comparison that carries tolerances."""
    within = _Within(world)
    plan = throughput.warm_up(within, traffic, seconds)
    return {**plan, "first": within.first}


def finish(world, plan: dict, win: dict, extra: dict | None) -> dict:
    rt = world.rt
    # to a step boundary, outside every clock
    rest = -rt.steps_run % (ref.BODIES - 1)
    codes = list(plan["codes"]) + ([rt.run(max_steps=rest)] if rest else [])
    ticks_all = rt.steps_run
    seen = world.observed()
    kept = world.invariant(seen, ticks_all)
    errors = error_counts(rt)
    st = rt.state
    spilled = int(np.asarray(st.dspill_count).sum()
                  + np.asarray(st.rspill_count).sum())
    overflowed = bool(np.asarray(st.spill_overflow).any())
    held = int((np.asarray(st.tail, np.int64)
                - np.asarray(st.head, np.int64)).sum())
    bad_codes = win["bad_codes"] + (extra["bad_codes"] if extra else 0) \
        + sum(c != 0 for c in codes)
    segments = win["segment_dispatched"] \
        + (extra["segment_dispatched"] if extra else [])
    uneven = sum(d != plan["k"] * world.n for d in segments)
    first = plan["first"]
    checks = {
        "reference_first_ticks": plan["reference_ok"],
        **{"first_ticks_" + k: v for k, v in first["checks"].items()},
        "run_returned_0": bad_codes == 0,
        **kept["checks"],
        "n_processed_is_ticks_x_bodies":
        rt.counter("n_processed") & MASK32 == (ticks_all * world.n) & MASK32,
        "every_segment_dispatched_k_x_bodies": uneven == 0,
        "world_holds_one_token_a_body": held == world.live,
        "nothing_rejected_or_lost": not any(errors.values()),
        "nothing_spilled": spilled == 0 and not overflowed,
    }
    rates = np.asarray(win["segment_dispatched"]) / np.asarray(win["segment_s"])
    return {
        "metrics": {"msgs_per_s": float(np.median(rates))},
        "attempted": win["dispatched"] + world.live,
        "failed": (first["off"] + kept["deficit"] + abs(held - world.live)
                   + sum(errors.values()) + spilled + int(overflowed)
                   + bad_codes + uneven),
        "checks": checks,
        "notes": {"k": plan["k"], "ticks_in_window": win["ticks"],
                  "ticks_in_all": ticks_all, "segments": win["segments"],
                  "held": held,
                  "mean_msgs_per_s": win["dispatched"] / win["wall_s"],
                  "first_ticks_error": first["read"], **kept["read"],
                  "segment_s": [round(x, 4) for x in win["segment_s"]],
                  **errors},
    }
