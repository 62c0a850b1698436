"""Throughput mode for a world whose messages carry payloads in the device
blob pool, allocated, filled, moved, read and freed inside the window.

The window is `throughput`'s: the same segments (`run(max_steps=K)` back
to back, each followed by a read of `n_processed`), the same K and the
same `msgs_per_s`, the median of the segments' rates — imported, not
copied. `msgs_per_s` counts what `n_processed` counts: one message a
dependency. Beside each segment's rate the window keeps the pool's
books as the program's run loop offers them (`run_loop_stats()["pool"]`:
read with the window's own fetch, no device round trip; a program that
does not offer them leaves the two metrics that read them out).

What differs is what `correct` means: the pool is held to its books and
the payloads to `reference_taskbench`'s replay, on the chip's own state:

  reference_first_ticks   after the warm-up's first `reference_ticks`
                          ticks, at full size: every point's `step`,
                          `acc`, `seen`, `mask`, `bad_inputs` equal the
                          replay's, bit for bit (integers: no
                          tolerance) — `bad_inputs` 0 says every pair of
                          every payload read so far was `(timestep,
                          src_point)`, every dependency came exactly
                          once; `n_processed` is the replay's count;
                          the pool holds exactly the live payloads, by
                          its used flags and by allocs - frees;
  the same after the last tick, outside the segments' clock;
  pool constant           where the program offers its books a window:
                          `blobs_in_use` = the live payloads at the end
                          of EVERY segment, and every segment allocated
                          and freed K x the live payloads;
  accounting              every segment dispatched K x the messages a
                          tick; nothing rejected, dead-lettered, sent
                          to a bad behaviour; no failed, over-budget or
                          remote blob access; `run()` returned 0.

A world for this mode offers: `rt`, `live` (messages, and payloads, a
tick), `check()` (the comparison and the books), `held()`, `errors()`.
"""

from __future__ import annotations

import numpy as np

from benchmarks.modes import throughput

OFF = "points_off"


def pool_books(rt) -> dict | None:
    """The pool's books up to the last retired window, or None from a
    program whose run loop does not keep them."""
    return rt.run_loop_stats().get("pool")


class _FirstTicks:
    """What `throughput.warm_up` compares, turned from the behaviours
    each actor ran into how far the system is off the replay: points
    and failed checks, against none."""

    def __init__(self, world):
        self.rt, self._world = world.rt, world
        self.first = None

    def counts(self) -> np.ndarray:
        self.first = self._world.check()
        return np.array([self.first[OFF]] + [
            not ok for ok in self.first["checks"].values()], np.int64)

    def reference(self, _ticks: int) -> np.ndarray:
        return np.zeros(1 + len(self.first["checks"]), np.int64)


def warm_up(world, traffic: dict, seconds: float) -> dict:
    """`throughput`'s warm-up (compile, first ticks against the
    reference, K, one segment of K ticks), the comparison being the
    replay on every point and the pool's books."""
    first = _FirstTicks(world)
    plan = throughput.warm_up(first, traffic, seconds)
    return {**plan, "first": first.first}


def _segments(world, plan: dict, until) -> dict:
    """`throughput`'s segments, the pool's books sampled where each
    ends (host integers the retire already brought)."""
    rt = world.rt
    books = [pool_books(rt)]

    def sampled(elapsed, n):
        books.append(pool_books(rt))
        return until(elapsed, n)
    win = throughput._run_segments(world, plan, sampled)
    if books[0] is not None:
        ends = books[1:]
        win["pool"] = {
            "allocs": ends[-1]["allocs"] - books[0]["allocs"],
            "frees": ends[-1]["frees"] - books[0]["frees"],
            "blobs_in_use": ends[-1]["blobs_in_use"],
            "slots": int(rt.opts.blob_slots),
            "segment_in_use": [b["blobs_in_use"] for b in ends],
            "segment_allocs": [b["allocs"] - a["allocs"]
                               for a, b in zip(books, ends)],
            "segment_frees": [b["frees"] - a["frees"]
                              for a, b in zip(books, ends)]}
    return win


def window(world, plan: dict, seconds: float) -> dict:
    return _segments(world, plan, lambda t, _n: t >= seconds)


def traced(world, plan: dict, units: int) -> dict:
    return _segments(world, plan, lambda _t, n: n >= units)


def _pool_off(world, k: int, *parts) -> int:
    """Segments whose books are not the world's: slots in use at the
    end, slots claimed, slots released."""
    off = 0
    for part in parts:
        pool = part and part.get("pool")
        if pool:
            off += sum(n != world.live for n in pool["segment_in_use"])
            off += sum(n != k * world.live for n in
                       pool["segment_allocs"] + pool["segment_frees"])
    return off


def finish(world, plan: dict, win: dict, extra: dict | None) -> dict:
    rt = world.rt
    errors = world.errors()
    last = world.check()
    first = plan["first"]
    bad_codes = win["bad_codes"] + (extra["bad_codes"] if extra else 0) \
        + sum(c != 0 for c in plan["codes"])
    segments = win["segment_dispatched"] \
        + (extra["segment_dispatched"] if extra else [])
    uneven = sum(d != plan["k"] * world.live for d in segments)
    pool_off = _pool_off(world, plan["k"], win, extra)
    checks = {
        "reference_first_ticks": plan["reference_ok"],
        **{"first_ticks_" + k: v for k, v in first["checks"].items()},
        "run_returned_0": bad_codes == 0,
        **last["checks"],
        "pool_constant_every_segment": pool_off == 0,
        "every_segment_dispatched_k_x_messages": uneven == 0,
        "error_counters_zero": not any(errors.values()),
    }
    rates = np.asarray(win["segment_dispatched"]) / np.asarray(win["segment_s"])
    return {
        "metrics": {"msgs_per_s": float(np.median(rates))},
        "attempted": win["dispatched"] + world.live,
        "failed": (first[OFF] + last[OFF] + sum(errors.values())
                   + sum(not ok for ok in last["checks"].values())
                   + bad_codes + uneven + pool_off),
        "checks": checks,
        "notes": {"k": plan["k"], "ticks_in_window": win["ticks"],
                  "ticks_in_all": rt.steps_run, "segments": win["segments"],
                  "mean_msgs_per_s": win["dispatched"] / win["wall_s"],
                  "points_off": last["off"], "books": last["books"],
                  "pool_in_window": {k: v for k, v in
                                     (win.get("pool") or {}).items()
                                     if not k.startswith("segment_")},
                  "segment_s": [round(x, 4) for x in win["segment_s"]],
                  **errors},
    }
