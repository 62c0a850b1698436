"""The least time a tick's route needs at one chip's peak HBM bandwidth
(`route_bytes.py`, `peaks.json`: a shard's share of the entries the
window's counters say a tick routes) / the device time the tick spent
under `pony/route` (`route_ms`, a mean over the chips). Bound by bytes:
the route computes nothing."""

from benchmarks import route_bytes
from benchmarks.layer_metrics import route_ms


def read(ctx):
    r = ctx["window"].get("route")
    ms = route_ms.read(ctx)
    if not r or not r["ticks"] or not ms or not ctx["peak"]:
        return None
    least_s = route_bytes.tick_min_seconds(
        r["routed"] / r["ticks"], r["shards"],
        int(ctx["cfg"]["runtime_options"]["msg_words"]), ctx["peak"])
    return 100.0 * least_s / (ms / 1e3)
