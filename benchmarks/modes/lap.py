"""Lap mode: the delay one message sees with nothing else in the world.

One token is sent with one lap of hops left and `Runtime.run()` is
called with no step limit: the token dies at the end of the lap and
`run()` returns 0 at quiescence, by itself. A lap's time is the wall
clock around the `send` and the `run()`; the hop time is the median
over all laps in the window of lap time / hops. Laps follow one another
until the asked seconds have passed.

A world for this mode offers: `rt`, `live`, `start_lap()` (seeds one
lap, returns its hops), `counts()` and `reference_laps(laps)`.
"""

from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from benchmarks.modes.common import error_counts, since, snapshot


def _lap(world):
    t0 = time.perf_counter()
    with TraceAnnotation("segment"):
        hops = world.start_lap()
        code = world.rt.run()
    return code, hops, time.perf_counter() - t0


def warm_up(world, traffic: dict, seconds: float) -> dict:
    """The empty world's first run() compiles or reloads the window and
    must quiesce at once; then a few laps let the window controller
    settle and are checked against the reference."""
    del seconds
    rt = world.rt
    codes = [rt.run()]
    empty_ok = codes[0] == 0 and int(world.counts().sum()) == 0
    laps = max(1, int(traffic["warm_laps"]))
    codes += [_lap(world)[0] for _ in range(laps)]
    reference_ok = empty_ok and bool(np.array_equal(
        world.counts(), world.reference_laps(laps)))
    return {"laps": laps, "codes": codes, "reference_ok": reference_ok}


def _run_laps(world, plan: dict, until) -> dict:
    rt = world.rt
    before = snapshot(rt)
    lap_s, codes, hops = [], [], 0
    t0 = time.perf_counter()
    while True:
        code, h, s = _lap(world)
        with TraceAnnotation("between-segments"):
            codes.append(code)
            hops += h
            lap_s.append(s)
            now = time.perf_counter()
        if until(now - t0, len(codes)):
            break
    plan["laps"] += len(codes)
    return {"wall_s": now - t0, "dispatched": hops, "lap_s": lap_s,
            "hops_per_lap": hops // len(codes), "segments": len(codes),
            "bad_codes": sum(c != 0 for c in codes), **since(rt, before)}


def window(world, plan: dict, seconds: float) -> dict:
    return _run_laps(world, plan, lambda t, _n: t >= seconds)


def traced(world, plan: dict, units: int) -> dict:
    return _run_laps(world, plan, lambda _t, n: n >= units)


def finish(world, plan: dict, win: dict, extra: dict | None) -> dict:
    rt = world.rt
    counts = world.counts()
    want = world.reference_laps(plan["laps"])
    errors = error_counts(rt)
    bad = win["bad_codes"] + (extra["bad_codes"] if extra else 0)
    checks = {
        "reference_first_laps": plan["reference_ok"],
        "run_returned_0": bad == 0 and all(c == 0 for c in plan["codes"]),
        "reference_every_actor": bool(np.array_equal(counts, want)),
        "error_counters_zero": not any(errors.values()),
    }
    hop_us = np.asarray(win["lap_s"]) / win["hops_per_lap"] * 1e6
    return {
        "metrics": {"hop_us": float(np.median(hop_us))},
        "attempted": win["dispatched"],
        "failed": int(np.abs(counts - want).sum()) + sum(errors.values())
        + bad,
        "checks": checks,
        "notes": {"laps_in_window": win["segments"],
                  "hops_per_lap": win["hops_per_lap"],
                  "ticks_in_window": win["ticks"], **errors},
    }
