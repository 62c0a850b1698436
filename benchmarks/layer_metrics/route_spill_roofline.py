"""The least time a tick's route needs to decide who mutes, at one
chip's peak HBM bandwidth (`route_spill_bytes.py`, `peaks.json`: a
shard's share of the entries the window's counters say a tick routes and
of the senders a tick mutes at routing) / the device time the tick spent
under `pony/route/spill` (`route_spill_ms`, a mean over the chips).
Bound by bytes: the lookup computes nothing."""

from benchmarks import route_spill_bytes
from benchmarks.layer_metrics import route_spill_ms


def read(ctx):
    r = ctx["window"].get("route")
    ms = route_spill_ms.read(ctx)
    if not r or not r["ticks"] or "remote_mutes" not in r or not ms \
            or not ctx["peak"]:
        return None
    least_s = route_spill_bytes.tick_min_seconds(
        r["routed"] / r["ticks"], r["remote_mutes"] / r["ticks"],
        r["shards"], ctx["peak"])
    return 100.0 * least_s / (ms / 1e3)
