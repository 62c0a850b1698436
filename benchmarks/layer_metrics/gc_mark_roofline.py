"""The least time a collector pass needs at the chip's peak HBM
bandwidth (`gc_bytes.py`, `peaks.json`; the hops a pass took are the
window's own, the queued messages the world's steady tick) / the device
time a pass took (`gc_pass_ms`). Bound by bytes: a trace is no FLOP."""

from benchmarks import gc_bytes
from benchmarks.layer_metrics import gc_pass_ms


def read(ctx):
    ms = gc_pass_ms.read(ctx)
    w = ctx["window"]
    if not ms or not ctx["peak"] or not w.get("passes"):
        return None
    least_s = gc_bytes.pass_min_seconds(
        w["rows"], 1, ctx["tick_shape"]["messages"],
        w["hops"] / w["passes"], ctx["peak"])
    return 100.0 * least_s / (ms / 1e3)
