"""The share of routed entries that left their shard in the timed
window: `n_routed_remote` / `n_routed`. The protocol's at a given
traffic ((p - 1) / p for uniform recipients), not a lever."""


def read(ctx):
    r = ctx["window"].get("route")
    if not r or not r["routed"]:
        return None
    return 100.0 * r["remote"] / r["routed"]
