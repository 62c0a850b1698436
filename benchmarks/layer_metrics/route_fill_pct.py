"""How much of what the route exchanges, and of the delivery list it
fills, is messages: entries the route shipped in the timed window
(`n_routed`) / the slots it exchanged (ticks x shards x shards x
`bucket`, `state.layout_sizes`). The rest is padding that every list
phase of delivery runs over. None from a program without the counters or
a world on one chip."""


def read(ctx):
    r = ctx["window"].get("route")
    if not r or not r["bucket"] or not r["ticks"]:
        return None
    slots = r["ticks"] * r["shards"] * r["shards"] * r["bucket"]
    return 100.0 * r["routed"] / slots
