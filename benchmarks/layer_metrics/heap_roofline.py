"""The least time a tick's heap updates need at the chip's peak HBM
bandwidth (`heap_bytes.py`, `peaks.json`) / the device time the tick
spent under `pony/dispatch/heap`. Bound by bytes: an xor is no FLOP."""

from benchmarks import heap_bytes
from benchmarks.layer_metrics import heap_update_ms


def read(ctx):
    ms = heap_update_ms.read(ctx)
    if not ms or not ctx["peak"]:
        return None
    least_s = heap_bytes.tick_min_seconds(ctx["cfg"], ctx["traffic"],
                                          ctx["peak"])
    return 100.0 * least_s / (ms / 1e3)
