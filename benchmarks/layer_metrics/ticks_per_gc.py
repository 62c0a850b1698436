"""Ticks a collector pass: the timed window's ticks / its passes. The
policy's at a given world, not a lever: the run loop collects when the
next tick's spawn reservations would outrun the free rows, so the epoch
is (rows free after a pass - rows a tick reserves) / rows a tick
spawns."""


def read(ctx):
    w = ctx["window"]
    if not w.get("passes"):
        return None
    return w["ticks"] / w["passes"]
