"""Device-busy time in the traced laps / ticks retired in them, in
microseconds: the device's share of one hop (one hop = one tick)."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["ticks"]:
        return None
    return 1e6 * t["busy_s"] / t["ticks"]
