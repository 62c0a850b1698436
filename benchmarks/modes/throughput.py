"""Throughput mode: behaviours dispatched per second of wall clock.

The world is driven by `Runtime.run(max_steps=K)` called back to back.
Each call is a segment; each ends in the run loop's own host fetch, so
the device's work is inside the clock, and is followed by a read of the
device's `n_processed` counter. A segment's rate is the behaviours
dispatched in it / the seconds from the end of the segment before it to
its own end. The window ends at the first segment boundary at or after
the asked seconds.

The end-to-end metric is the MEDIAN of the segments' rates, not the
window's total / its wall clock: the chip machine's host stalls for
seconds now and then (one run in 29 lost 9 s of a 30 s window, PERF.md
PR 22), and a mean would make that the program's. What a mean would
show and the median hides — a program that stalls periodically — is
reported beside it as the layer metric `slow_segment_pct`, and both
rates are printed.

K is set once, after the warm-up: the largest power of two whose
segment, at the warm-up's tick time, lasts at most seconds/16, and at
least 1 — so the segments stay about as long when the tick gets a
hundred times faster.

A world for this mode offers: `rt`, `live` (messages seeded),
`counts()` (behaviours run per actor), `reference(ticks)` (the plain
reference, tick by tick) and `reference_closed(ticks)` (the same for any
number of ticks, or None where no closed form exists).
"""

from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from benchmarks.modes.common import error_counts, since, snapshot

SEGMENTS_PER_WINDOW = 16
MASK32 = 0xFFFFFFFF


def _segment(rt, k: int, before: int):
    """One segment and the read of the device counter that follows it:
    (run()'s return code, behaviours dispatched, counter now). The
    device counter is 32 bits wide and wraps, so the difference is taken
    mod 2**32 and summed in Python integers by the caller."""
    with TraceAnnotation("segment"):
        code = rt.run(max_steps=k)
    with TraceAnnotation("between-segments"):
        after = rt.counter("n_processed") & MASK32
    return code, (after - before) & MASK32, after


def warm_up(world, traffic: dict, seconds: float) -> dict:
    """Set-up's last part: compile or reload the window program, check
    the first ticks against the reference, choose K, and run one
    segment of K ticks so that every program the window calls (the
    pipelined dispatch among them) has run once."""
    rt = world.rt
    ref_ticks = max(2, int(traffic["reference_ticks"]))
    codes = [rt.run(max_steps=1)]
    t0 = time.perf_counter()
    codes.append(rt.run(max_steps=ref_ticks - 1))
    tick_s = (time.perf_counter() - t0) / (ref_ticks - 1)
    reference_ok = bool(np.array_equal(world.counts(),
                                       world.reference(rt.steps_run)))
    k = 1
    while 2 * k * tick_s <= seconds / SEGMENTS_PER_WINDOW:
        k *= 2
    code, _, counter = _segment(rt, k, rt.counter("n_processed") & MASK32)
    codes.append(code)
    return {"k": k, "warm_tick_s": tick_s, "reference_ok": reference_ok,
            "codes": codes, "counter": counter}


def _run_segments(world, plan: dict, until) -> dict:
    """Segments back to back until `until(elapsed, segments)` says stop."""
    rt = world.rt
    k, counter = plan["k"], plan["counter"]
    before = snapshot(rt)
    seg_done, codes, seg_s = [], [], []
    t0 = last = time.perf_counter()
    while True:
        code, done, counter = _segment(rt, k, counter)
        now = time.perf_counter()
        seg_done.append(done)
        codes.append(code)
        seg_s.append(now - last)
        last = now
        if until(now - t0, len(codes)):
            break
    plan["counter"] = counter
    return {"wall_s": last - t0, "dispatched": sum(seg_done),
            "segments": len(codes), "segment_s": seg_s,
            "segment_dispatched": seg_done,
            "bad_codes": sum(c != 0 for c in codes), **since(rt, before)}


def window(world, plan: dict, seconds: float) -> dict:
    """The timed window."""
    return _run_segments(world, plan, lambda t, _n: t >= seconds)


def traced(world, plan: dict, units: int) -> dict:
    """`units` more segments, for the profiler (the caller traces)."""
    return _run_segments(world, plan, lambda _t, n: n >= units)


def finish(world, plan: dict, win: dict, extra: dict | None) -> dict:
    """Checks outside the timed window, the accounting and the
    end-to-end metric. `extra` is the traced part's record (its ticks
    ran too), or None."""
    rt = world.rt
    ticks_all = rt.steps_run
    counts = world.counts()
    dispatched_all = win["dispatched"] + (extra["dispatched"] if extra else 0)
    errors = error_counts(rt)
    # queue_depth() over every actor at once: what the world still holds
    held = int((np.asarray(rt.state.tail, np.int64)
                - np.asarray(rt.state.head, np.int64)).sum())
    checks = {
        "reference_first_ticks": plan["reference_ok"],
        "run_returned_0": win["bad_codes"] == 0
        and all(c == 0 for c in plan["codes"])
        and (extra is None or extra["bad_codes"] == 0),
        "world_holds_seeded_messages": held == world.live,
        "error_counters_zero": not any(errors.values()),
        # every behaviour the device counted, some actor counted too
        "counts_sum_is_n_processed":
        int(counts.sum()) & MASK32 == rt.counter("n_processed") & MASK32,
    }
    closed = world.reference_closed(ticks_all)
    unaccounted = 0
    if closed is not None:
        checks["reference_every_actor"] = bool(np.array_equal(counts, closed))
        unaccounted = int(np.abs(counts - closed).sum())
        window_ticks = win["ticks"] + (extra["ticks"] if extra else 0)
        checks["dispatched_is_ticks_x_live"] = \
            dispatched_all == window_ticks * world.live
    failed = (abs(held - world.live) + sum(errors.values()) + unaccounted
              + win["bad_codes"])
    rates = np.asarray(win["segment_dispatched"]) / np.asarray(win["segment_s"])
    return {
        "metrics": {"msgs_per_s": float(np.median(rates))},
        "attempted": win["dispatched"] + world.live,
        "failed": failed,
        "checks": checks,
        "notes": {"k": plan["k"], "ticks_in_window": win["ticks"],
                  "segments": win["segments"], "held": held,
                  "mean_msgs_per_s": win["dispatched"] / win["wall_s"],
                  "segment_s": [round(x, 4) for x in win["segment_s"]],
                  **errors},
    }
