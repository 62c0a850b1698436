"""The inside twin of `slow_segment_pct`, from the flight recorder's
window records: share of the timed window's wall clock that its slow
run-loop windows took beyond the median, split by who had the clock.

A record's time is `since_prev_ms + wall_ms` (consecutive records tile
the wall clock); its excess is what that exceeds the median time a tick
x its ticks. The part of the excess that is also an excess of `wait_ms`
(the host blocked on the device) over the median wait a tick is the
device's: this file. The rest (dispatch, host work, `run()`'s entry and
exit, the time between two calls) is the host's:
`slow_window_host_pct`.

The recorder keeps the newest 64 windows. The traced part ran after the
timed window, so its windows are counted back by their ticks first and
the timed window's `run_loop_windows` records are taken before them:
the profiler's start-up is in none of them. None where the ring no
longer holds them all, or the program has no such records."""

import statistics


def split(ctx):
    """(device %, host %) or None."""
    try:
        from ponyc_tpu import flight
        recorder = flight.latest()
    except (ImportError, AttributeError):
        return None
    trace, win = ctx.get("trace"), ctx["window"]
    if recorder is None or not trace or not trace.get("ticks"):
        return None
    records = list(recorder.windows)
    left = trace["ticks"]
    while left > 0 and records:
        left -= records.pop()["ticks"]
    want = win["run_loop_windows"]
    if left != 0 or want < 1 or len(records) < want:
        return None
    records = records[-want:]
    if any("wall_ms" not in r for r in records) or \
            sum(r["ticks"] for r in records) != win["ticks"]:
        return None
    took = [r["since_prev_ms"] + r["wall_ms"] for r in records]
    tick = statistics.median(t / r["ticks"] for t, r in zip(took, records))
    wait = statistics.median(r["wait_ms"] / r["ticks"] for r in records)
    device = host = 0.0
    for t, r in zip(took, records):
        excess = max(0.0, t - tick * r["ticks"])
        waited = min(excess, max(0.0, r["wait_ms"] - wait * r["ticks"]))
        device += waited
        host += excess - waited
    return 100.0 * device / sum(took), 100.0 * host / sum(took)


def read(ctx):
    parts = split(ctx)
    return None if parts is None else parts[0]
