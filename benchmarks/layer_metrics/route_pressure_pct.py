"""The share of the timed window's shard-ticks on which routing looked
its sorted entries' targets up in the mesh-wide hot word: the movement
of `n_route_pressure` / (shards x ticks). 0 on a quiet mesh, 100 where
someone is overloaded or declares pressure on every tick. None where the
mode does not read the counter."""


def share(ctx, key: str):
    r = ctx["window"].get("route")
    if not r or key not in r or not r["ticks"] or not r["shards"]:
        return None
    return 100.0 * r[key] / (r["shards"] * r["ticks"])


def read(ctx):
    return share(ctx, "lookups")
