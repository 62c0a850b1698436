"""What both modes read from the program: the run loop's own counters
around a stretch of driving, and the error counters after it."""

from __future__ import annotations

ERROR_COUNTERS = ("n_rejected", "n_badmsg", "n_deadletter")


def snapshot(rt) -> dict:
    s = rt.run_loop_stats()
    return {"ticks": rt.steps_run,
            "host_gap_us": s["host_gap_us_total"],
            "sync_dispatches": s["sync_dispatches"],
            "pipelined_dispatches": s["pipelined_dispatches"],
            "run_loop_windows": s["windows"]}


def since(rt, before: dict) -> dict:
    """Ticks retired, host gap and dispatches since `before`."""
    after = snapshot(rt)
    return {k: after[k] - before[k] for k in after}


def error_counts(rt) -> dict:
    return {c: rt.counter(c) for c in ERROR_COUNTERS}
