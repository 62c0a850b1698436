"""1 - device busy / traced span, from the same trace."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["span_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["span_s"])
