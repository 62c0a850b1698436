"""How long the idle chip waits for a launch: for each sync-point
window of the traced part, from the start of its `pony:dispatching`
span to the start of the first device operation after it. The median
over those windows, in microseconds.

This file and `wait_return_us.py` split a window's two idle stretches
where the host's clock and the device's meet, in the trace
(`phase_trace.load`: the device's operations and the `pony:` spans with
their `window=`):

  launch lead   a dispatch is at a sync point when every earlier
                window's `pony:wait` had ended by its start (a pipelined
                one starts before the window ahead of it is retired);
                the device is then idle until the launch reaches it. The
                first operation must start before the window's own wait
                ends.
  way back      for a `pony:wait` span at whose end the device is idle
                (no pipelined window runs on behind it): the span's end
                - the end of the last device operation that ended inside
                it: the answer's way to the host and the host's waking.

None where no traced window has such a stretch (a trace without device
operations, a program without the spans)."""

import bisect
import os
import statistics

from benchmarks import phase_trace, reduce_trace

DISPATCHING = phase_trace.SPAN_PREFIX + "dispatching"
WAIT = phase_trace.SPAN_PREFIX + "wait"


def stretches(data: dict) -> dict:
    """`phase_trace.load()` data -> {"lead": [ns, ...], "back": [ns,
    ...]}, an entry a window that has the stretch."""
    ops = sorted((s, s + d) for line in data["device"]
                 for _name, s, d, _op in line)
    starts = [a for a, _b in ops]
    ends = sorted(b for _a, b in ops)
    busy_to, reach = [], float("-inf")   # latest end among ops[:i + 1]
    for _a, b in ops:
        reach = max(reach, b)
        busy_to.append(reach)
    waits = {w: (s, s + d) for name, s, d, w, _t in data["host"]
             if name == WAIT and w is not None}
    lead, back = [], []
    for name, s, _d, w, _t in data["host"]:
        if name != DISPATCHING or w is None:
            continue
        if any(e > s for v, (_s, e) in waits.items() if v < w):
            continue                    # rode behind an unretired window
        i = bisect.bisect_left(starts, s)
        retired = waits[w][1] if w in waits else float("inf")
        if i < len(starts) and starts[i] <= retired:
            lead.append(starts[i] - s)
    for s, e in waits.values():
        i = bisect.bisect_right(starts, e) - 1
        if i >= 0 and busy_to[i] > e:
            continue                    # the device runs on: no idle here
        j = bisect.bisect_right(ends, e) - 1
        if j >= 0 and ends[j] >= s:
            back.append(e - ends[j])
    return {"lead": lead, "back": back}


_cache: dict = {}


def of_run() -> dict | None:
    """The stretches of this run's trace, parsed once a process."""
    path = reduce_trace.find_xplane(phase_trace.TRACE_DIR)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        _cache.clear()
        _cache[key] = stretches(phase_trace.load(path))
    return _cache[key]


def median_us(which: str) -> float | None:
    found = of_run()
    if not found or not found[which]:
        return None
    return statistics.median(found[which]) / 1e3


def read(ctx):
    return median_us("lead")
