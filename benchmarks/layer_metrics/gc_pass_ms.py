"""Device time one collector pass takes: the device-busy time that lies
inside the run loop's `pony:gc` spans (the host holds the span open
until the pass has answered, and a pass the rows asked for has no
window in flight beside it) / those spans, over the traced part. The
collector is a program of its own, and its operations are found by WHEN
they ran, not by name: `phase_trace` keys op_names by event name, and
two programs number their fusions alike. The time by the collector's own
scopes (`pony/gc_mark/roots`, `/hop`, `/sweep`) is printed beside it.
None where the traced part holds no pass."""

from benchmarks import phase_trace, reduce_trace
from benchmarks.reduce_trace import _union

SPAN = phase_trace.SPAN_PREFIX + "gc"
_cache: dict = {}


def passes(ctx):
    """(device-busy seconds inside the traced `pony:gc` spans, spans);
    the spans are the run loop's own (they carry `window=`; a pass's
    zero-length outcome marker does not)."""
    path = reduce_trace.find_xplane(phase_trace.TRACE_DIR)
    if path is None or not ctx.get("trace"):
        return None
    if path not in _cache:
        _cache.clear()
        data = phase_trace.load(path)
        spans = [(h[1], h[1] + h[2]) for h in data["host"]
                 if h[0] == SPAN and h[3] is not None]
        busy = 0.0
        for events in data["device"]:
            merged = _union([e[1], e[1] + e[2]] for e in events)
            starts = [m[0] for m in merged]
            busy += sum(phase_trace._busy_within(merged, starts, lo, hi)
                        for lo, hi in spans)
        _cache[path] = (busy / max(1, len(data["device"])) / 1e9, len(spans))
        by_scope = phase_trace.under(phase_trace.of_run(ctx), "gc_mark")
        print(f"collector: {_cache[path][1]} traced passes, device busy "
              f"{_cache[path][0]:.4f}s inside their spans; by scope "
              f"pony/gc_mark/**: {by_scope}", flush=True)
    return _cache[path]


def read(ctx):
    found = passes(ctx)
    if not found or not found[1]:
        return None
    return 1e3 * found[0] / found[1]
