"""The least time a tick's traffic needs at the chip's peak HBM
bandwidth (`min_bytes.py`, `peaks.json`) / the device time a tick took.
Bound by bytes: the tick is integer record movement, no FLOPs."""

from benchmarks import min_bytes


def read(ctx):
    t = ctx["trace"]
    if not t or not t["ticks"] or not t["busy_s"]:
        return None
    least_s = min_bytes.tick_min_seconds(ctx["tick_shape"], ctx["peak"])
    return 100.0 * least_s / (t["busy_s"] / t["ticks"])
