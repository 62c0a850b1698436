"""Device-busy time in the traced segments / ticks retired in them.
The gap to tick_ms is the host's share of a tick."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["ticks"]:
        return None
    return 1e3 * t["busy_s"] / t["ticks"]
