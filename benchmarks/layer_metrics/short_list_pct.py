"""The share of the timed window's shard-ticks that delivered over the
SHORT list (what arrived fitted one shard's outbox and the received
buckets were joined front to front, `route._route_unpack`): the movement
of `n_unpacked` / (shards x ticks). The rest ran the long list."""

from benchmarks.layer_metrics.route_pressure_pct import share


def read(ctx):
    return share(ctx, "unpacked")
