"""What the launch of one window costs the host where `msgs_per_s` is
taken: the median `dispatch_ms` (start of the dispatch to the launch's
return, the `pony:dispatching` span, always on) over the flight
recorder's records of the TIMED window, tracing off. `window_host_us`
times the same stretch, with the host's work after the retire, over the
two or three windows of the traced part.

The records are chosen as `slow_window_device_pct.split` chooses them:
the traced part's windows are counted back by their ticks, the timed
window's `run_loop_windows` records are taken before them. None where
the ring of 64 no longer holds them all, or the records have no
`dispatch_ms`."""

import statistics

from benchmarks.layer_metrics.setup_build_s import recorder


def timed_records(ctx):
    found = recorder()
    trace, win = ctx.get("trace"), ctx["window"]
    if found is None or not trace or not trace.get("ticks"):
        return None
    records = list(found.windows)
    left = trace["ticks"]
    while left > 0 and records:
        left -= records.pop()["ticks"]
    want = win["run_loop_windows"]
    if left != 0 or want < 1 or len(records) < want:
        return None
    records = records[-want:]
    if sum(r["ticks"] for r in records) != win["ticks"]:
        return None
    return records


def read(ctx):
    records = timed_records(ctx)
    if not records or any("dispatch_ms" not in r for r in records):
        return None
    return 1e3 * statistics.median(r["dispatch_ms"] for r in records)
