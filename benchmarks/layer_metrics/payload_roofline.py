"""The least time a tick's payloads need at the chip's peak HBM
bandwidth (`payload_bytes.py`, `peaks.json`) / the device time the tick
spent at and below `pony/dispatch/heap`. Bound by bytes: a compare and
an add are no FLOPs."""

from benchmarks import payload_bytes
from benchmarks.layer_metrics import heap_update_ms


def read(ctx):
    ms = heap_update_ms.read(ctx)
    if not ms or not ctx["peak"]:
        return None
    least_s = payload_bytes.tick_min_seconds(ctx["cfg"], ctx["traffic"],
                                             ctx["peak"])
    return 100.0 * least_s / (ms / 1e3)
