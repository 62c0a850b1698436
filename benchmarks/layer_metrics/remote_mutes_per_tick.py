"""Senders a tick that routing muted behind a receiver on ANOTHER shard:
the timed window's movement of `n_remote_mutes` / its ticks. The
protocol's at a given seed, not a lever. None where the mode does not
read the counter."""


def read(ctx):
    r = ctx["window"].get("route")
    if not r or "remote_mutes" not in r or not r["ticks"]:
        return None
    return r["remote_mutes"] / r["ticks"]
