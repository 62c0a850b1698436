"""Throughput mode for a world whose population moves: behaviours create
actors, only the collector frees them.

`msgs_per_s` is what the other throughput modes report — behaviours
dispatched / seconds of wall clock, the MEDIAN over the window's
segments, each segment a `Runtime.run(max_steps=K)` followed by a read
of `n_processed` (`throughput._segment`, imported) — but a segment here
is a whole number of COLLECTION EPOCHS. An epoch is the ticks up to and
including one collector pass: the run loop collects when the device
says the next tick's spawn reservations would outrun the free rows,
which in a closed deterministic world happens every E ticks exactly.
The warm-up runs tick by tick, reads E off the passes it sees (the
ticks between the last two), and stops right after a pass; K = m x E
with m the largest whole number whose segment lasts at most seconds/16
(at least 1). Every segment then begins on the first tick of an epoch
and holds exactly m passes, so no segment differs from the next by a
pass; `finish` checks that (`epochs_whole`) and the per-layer metrics
`ticks_per_gc`, `gc_hops_per_pass`, `gc_wall_pct` and
`free_rows_low_pct` read the window's own counters.

What `correct` means (`reference_spreader.py`; all integer, all exact):

  reference_first_ticks  the warm-up's first `reference_ticks` ticks at
                         full size equal `Forest` on the per-tick
                         counts (spawns, dispatches) and, after them,
                         `invariant` holds on every root and row (at
                         least two passes inside);
  invariant_*            after the window's last tick: every root's
                         `runs` the reference's and `total` = `runs` x
                         the tree's actors; device spawns the
                         reference's; spawned - collected = alive
                         non-root rows; nothing the reference's trace
                         keeps is dead;
  collector_exact        after one forced `rt.gc()`, outside the timed
                         window: alive == reachable, row for row;
  error_counters_zero    no reject, bad message, dead letter, destroy or
                         refused spawn; `run()` returned 0 every time.

A world for this mode offers: `rt`, `live`, `reference()`, `check()`,
`counters()`, `held()`, `errors()`, `roots`, `n`.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.modes import throughput
from benchmarks.modes.throughput import (MASK32, SEGMENTS_PER_WINDOW,
                                         _segment)

OFF = ("roots_off", "spawned_off", "rows_off", "lost")


def _tick(world) -> dict:
    """One tick, and the counters after it."""
    rt = world.rt
    before = rt.counter("n_processed") & MASK32
    code = rt.run(max_steps=1)
    now = world.counters()
    now["code"] = code
    now["dispatches"] = ((rt.counter("n_processed") & MASK32) - before) \
        & MASK32
    return now


def warm_up(world, traffic: dict, seconds: float) -> dict:
    """Set-up's last part: compile or reload the window and the
    collector, hold the first ticks to the reference, find the epoch,
    stop right after a pass, choose K, run one segment of K ticks."""
    rt = world.rt
    ref_ticks = int(traffic["reference_ticks"])
    codes, pass_ticks, tick_s = [], [], []
    ticks_ok = True
    last = world.counters()
    reference = world.reference()
    while True:
        t0 = time.perf_counter()
        now = _tick(world)
        tick_s.append(time.perf_counter() - t0)
        codes.append(now["code"])
        if rt.steps_run <= ref_ticks:
            want = reference.tick()
            ticks_ok = ticks_ok \
                and now["spawned"] - last["spawned"] == want["spawns"] \
                and now["dispatches"] == want["dispatches"]
        if now["passes"] != last["passes"]:
            pass_ticks.append(rt.steps_run)
        last = now
        # on: the reference's ticks, two passes (an epoch between them),
        # and the tick just run ended in a pass
        if rt.steps_run >= ref_ticks and len(pass_ticks) >= 2 \
                and pass_ticks[-1] == rt.steps_run:
            break
        if rt.steps_run >= 64 * ref_ticks:
            raise RuntimeError(f"no two collector passes in {rt.steps_run} "
                               "ticks: the world does not churn")
    found = world.check()
    reference_ok = ticks_ok and not any(found[k] for k in OFF)
    epoch = pass_ticks[-1] - pass_ticks[-2]
    # a tick's seconds without the pass's: the median; an epoch's: the
    # last whole one by the clock
    epoch_s = sum(tick_s[-epoch:])
    m = 1
    while 2 * m * epoch_s <= seconds / SEGMENTS_PER_WINDOW:
        m *= 2
    k = m * epoch
    code, _, counter = _segment(rt, k, rt.counter("n_processed") & MASK32)
    codes.append(code)
    return {"k": k, "epoch": epoch, "epochs_per_segment": m,
            "warm_tick_s": float(np.median(tick_s)), "epoch_s": epoch_s,
            "reference_ok": reference_ok, "first_ticks": found,
            "pass_ticks": pass_ticks, "codes": codes, "counter": counter}


def _run_segments(world, plan: dict, until) -> dict:
    """`throughput._run_segments`, with the collector's passes counted
    after every segment (inside `until`, which runs once a segment has
    been timed: as there, a read between two segments is part of the
    next one's clock) and its counters read before and after."""
    rt = world.rt
    c0 = world.counters()
    passes, seg_passes = c0["passes"], []

    def counted(elapsed, segments):
        nonlocal passes
        at = rt.run_loop_stats()["gc_runs"]
        seg_passes.append(at - passes)
        passes = at
        return until(elapsed, segments)

    out = throughput._run_segments(world, plan, counted)
    c1 = world.counters()
    return {**out, "segment_passes": seg_passes,
            "passes": c1["passes"] - c0["passes"],
            "hops": c1["hops"] - c0["hops"],
            "gc_s": c1["gc_s"] - c0["gc_s"],
            "spawned": (c1["spawned"] - c0["spawned"]) & MASK32,
            "collected": (c1["collected"] - c0["collected"]) & MASK32,
            "free_rows_low": c1["free_rows_low"], "rows": int(world.n)}


def window(world, plan: dict, seconds: float) -> dict:
    """The timed window."""
    return _run_segments(world, plan, lambda t, _n: t >= seconds)


def traced(world, plan: dict, units: int) -> dict:
    """`units` more segments, for the profiler (the caller traces)."""
    return _run_segments(world, plan, lambda _t, n: n >= units)


def finish(world, plan: dict, win: dict, extra: dict | None) -> dict:
    rt = world.rt
    kept = world.check()                  # after the last tick
    rt.gc()                               # forced, outside the window
    exact = world.check()
    errors = world.errors()
    bad_codes = win["bad_codes"] + (extra["bad_codes"] if extra else 0) \
        + sum(c != 0 for c in plan["codes"])
    whole = all(p == plan["epochs_per_segment"] for p in
                win["segment_passes"] + (extra["segment_passes"]
                                         if extra else []))
    checks = {
        "reference_first_ticks": plan["reference_ok"],
        "run_returned_0": bad_codes == 0,
        **{"invariant_" + key: kept[key] == 0 for key in OFF},
        "collector_exact": exact["lost"] == 0 and exact["garbage"] == 0,
        "error_counters_zero": not any(errors.values()),
        "epochs_whole": whole,
    }
    held = world.held()
    rates = np.asarray(win["segment_dispatched"]) / np.asarray(win["segment_s"])
    return {
        "metrics": {"msgs_per_s": float(np.median(rates))},
        "attempted": win["dispatched"] + held,
        "failed": (sum(kept[k] for k in OFF) + exact["lost"]
                   + exact["garbage"] + sum(errors.values()) + bad_codes),
        "checks": checks,
        "notes": {"k": plan["k"], "epoch": plan["epoch"],
                  "ticks_in_window": win["ticks"],
                  "segments": win["segments"], "held": held,
                  "passes": win["passes"], "hops": win["hops"],
                  "gc_s": round(win["gc_s"], 4),
                  "spawned": win["spawned"], "collected": win["collected"],
                  "free_rows_low": win["free_rows_low"],
                  "garbage_at_end": kept["garbage"],
                  "pass_ticks_warm_up": plan["pass_ticks"],
                  "mean_msgs_per_s": win["dispatched"] / win["wall_s"],
                  "segment_s": [round(x, 4) for x in win["segment_s"]],
                  "segment_passes": win["segment_passes"], **errors},
    }
